"""Small readings shared by the run and the tools."""

from __future__ import annotations

import dataclasses
from typing import Any

GIB = 1024 ** 3


def step_memory(compiled) -> dict:
    """The compiled step's device footprint, from the compiler's own
    ``memory_analysis()``: arguments + outputs + temporaries - aliased
    (donated arguments that the outputs reuse), per device."""
    m = compiled.memory_analysis()
    parts = {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
        "generated_code_bytes": int(m.generated_code_size_in_bytes),
    }
    parts["step_bytes"] = (parts["argument_bytes"] + parts["output_bytes"]
                           + parts["temp_bytes"] - parts["alias_bytes"])
    parts["step_gib"] = parts["step_bytes"] / GIB
    return parts


@dataclasses.dataclass
class RunRecords:
    """Everything one run recorded, as a per-layer metric's reader sees
    it. A reader that finds nothing to read returns ``None``."""

    cell: Any                     # manifest.Cell
    window: dict                  # WindowHook.summary()
    startup: dict                 # the program's ``startup`` event fields
    step_memory: dict             # step_memory(compiled) of the timed step
    peaks: dict                   # peaks.json row of this device_kind
    model_flops_per_unit: float   # benchmarks/flops, mean over the pool
    attention_work: dict | None   # per chip and step, where the family has it
    trace: Any = None             # trace_reduce.TraceReduction, traced runs
