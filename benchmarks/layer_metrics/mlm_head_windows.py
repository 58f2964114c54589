"""Windows of each row's positions that the MLM head and its loss ran on
per step: the program's ``mlm_head_windows`` counter, mean over the steps
the window fetched. 1.0 is a step whose first window (sized from
``data.mask_prob``) held every labelled position of every row; each
further window is the head's forward and backward once more on that many
positions, for the same result."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "models"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    return scope_times.mean_counter(__file__, r, "mlm_head_windows")
