"""Tokens of the fullest held expert over the mean of the held experts,
per expert layer and step (the program's ``moe_load_max_mean`` counter),
mean over the steps the window fetched. 1.0 is a perfectly even load; a
grouped product's time follows the sum, a padded or capacity layout's
would follow this."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "expert layer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    return scope_times.mean_counter(__file__, r, "moe_load_max_mean")
