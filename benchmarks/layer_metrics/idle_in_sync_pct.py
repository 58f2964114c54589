"""The share of the traced stretch in which the device idled inside a
sync bubble of the loop: the ten longest idle gaps of the first device
(``trace_reduce.reduce_device``), cut to the bubbles of
``sync_bubble_ms_step`` on the profile's clock, over that device's
traced window. A bubble runs from the end of a ``metrics_fetch`` span of
the program's loop timeline to the end of the next ``train_step`` span:
the same interval as the host metric, so what shortens the one shortens
the other. The idle *before* the fetch returns (the device has drained,
the host still waits) is ``idle_in_fetch_pct``'s; ``device_idle_pct``
less both is idle that the loop's syncs do not explain. The timeline's
``start_ns`` are epoch nanoseconds; a trace's event times count from its
``profile_start_time``."""

from benchmarks.harness import loop_timeline

LAYER = "train loop"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    return loop_timeline.idle_pct_inside(
        __file__, r, lambda spans: [
            (start, end) for start, end, _ in loop_timeline.sync_bubbles(spans)])
