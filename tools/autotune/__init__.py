"""tools.autotune — goodput-driven config search for chip windows.

The library behind ``scripts/autotune.py`` (stdlib-only, same layout
discipline as tools/graftcheck): typed SearchSpace specs over the real
config dataclasses (space), an analytic roofline/traffic pruner that
skips configs predicted worse than the incumbent on the binding resource
(model, backed by core/roofline), supervised subprocess trials, one
child at a time under a stdlib-only parent (runner), the
resumable dtf-autotune-journal/1 trial journal (journal), goodput-
weighted scoring off dtf-run-summary/1 (scoring), the dtf-leaderboard/1
regression pin bench.py reads back (leaderboard), the chip_window plan
compiler that subsumed scripts/chip_window_queue.sh (plan), and the
search loop tying them together (search). docs/PERFORMANCE.md
"Autotuning" is the operator manual.
"""

from tools.autotune.journal import (  # noqa: F401
    JOURNAL_SCHEMA,
    JournalError,
    TrialJournal,
)
from tools.autotune.leaderboard import (  # noqa: F401
    LEADERBOARD_SCHEMA,
    config_digest,
    load_board,
    pin_entry,
    write_best_yaml,
)
from tools.autotune.model import (  # noqa: F401
    Factors,
    TrafficProfile,
    predict_candidate,
    prune_decision,
)
from tools.autotune.plan import (  # noqa: F401
    PlannedTrial,
    compile_chip_window_plan,
    format_plan,
)
from tools.autotune.runner import (  # noqa: F401
    FakeRunner,
    SubprocessRunner,
    TrialResult,
    TrialRunError,
)
from tools.autotune.scoring import (  # noqa: F401
    RUN_SUMMARY_SCHEMA,
    goodput_frac,
    score_trial,
)
from tools.autotune.search import (  # noqa: F401
    pin_winner,
    run_plan,
    run_space_search,
    trial_id_for,
)
from tools.autotune.space import (  # noqa: F401
    Knob,
    SearchSpace,
    SearchSpaceError,
)
