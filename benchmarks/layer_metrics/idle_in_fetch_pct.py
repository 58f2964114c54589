"""The share of the traced stretch in which the device idled while the
loop still waited in ``metrics_fetch``: the ten longest idle gaps of the
first device, cut to the ``metrics_fetch`` spans of the program's loop
timeline on the profile's clock, over that device's traced window. The
device is busy for as long as steps are in flight; it drains some
milliseconds before ``jax.device_get`` returns (the metrics' copy to the
host follows the last step), and that tail is idle which no host span
can see: the host only waits. Where the fetch ends, the sync bubble of
``sync_bubble_ms_step`` and ``idle_in_sync_pct`` begins."""

from benchmarks.harness import loop_timeline

LAYER = "train loop"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    return loop_timeline.idle_pct_inside(__file__, r, loop_timeline.fetches)
