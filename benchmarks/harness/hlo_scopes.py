"""From a compiled step's HLO text to what each instruction is.

A device trace names an operation by its HLO instruction (``fusion.123``,
``_flash_fwd.12``, ``all-reduce.3``); what it *does* is in the compiled
module's text: its opcode, the computation a fusion calls (and whether
that holds a convolution, which on a TPU is what a ``dot`` has become),
the custom-call target, and the ``op_name`` metadata that carries the
program's ``jax.named_scope`` path. This module reads that text once and
answers, by instruction name, with a category and a short scope label.
"""

from __future__ import annotations

import dataclasses
import re

COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_OPCODE = re.compile(r"^([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_SHAPE = re.compile(r"\b(\w+)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    op_name: str = ""
    calls: str = ""
    target: str = ""
    result_shapes: tuple = ()    # ((dtype, dims), ...) of the result type


def _split_type(rest: str) -> tuple[str, str]:
    """``rest`` starts with the result type; return (type, what follows)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
        return rest, ""
    head, _, tail = rest.partition(" ")
    return head, tail.lstrip()


def _shapes(text: str) -> tuple:
    return tuple((d, tuple(int(x) for x in dims.split(",") if x))
                 for d, dims in _SHAPE.findall(text))


class HloScopes:
    def __init__(self, hlo_text: str):
        self.instrs: dict[str, Instr] = {}
        self.computation_opcodes: dict[str, set] = {}
        current = None
        for line in hlo_text.splitlines():
            m = _INSTR.match(line)
            if m and " = " in line:
                name, rest = m.group(1), m.group(2)
                type_text, tail = _split_type(rest)
                op = _OPCODE.match(tail)
                if not op:
                    continue
                opcode = op.group(1)
                if current is not None:
                    self.computation_opcodes[current].add(opcode)
                # Keep the cost down on 8 MB texts: only the attributes
                # before backend_config (a kernel's body is ~100 kB there).
                attrs = tail.split(", backend_config=", 1)[0]
                calls = _CALLS.search(attrs)
                target = _TARGET.search(attrs)
                op_name = _OP_NAME.search(attrs)
                self.instrs[name] = Instr(
                    name=name, opcode=opcode,
                    op_name=op_name.group(1) if op_name else "",
                    calls=calls.group(1) if calls else "",
                    target=target.group(1) if target else "",
                    result_shapes=_shapes(type_text)
                    if opcode == "custom-call" else ())
                continue
            c = _COMPUTATION.match(line)
            if c and "=" not in line.split("{")[0].split("(")[0]:
                current = c.group(1)
                self.computation_opcodes.setdefault(current, set())

    # -- lookups ------------------------------------------------------------
    def find(self, event_name: str) -> Instr | None:
        """A trace event's name to its instruction: ``%fusion.1 = ...``,
        ``fusion.1`` and ``fusion.1:...`` all mean ``fusion.1``."""
        name = event_name.strip().lstrip("%")
        for sep in (" = ", " ", ":"):
            name = name.split(sep, 1)[0]
        return self.instrs.get(name)

    def holds_matmul(self, instr: Instr) -> str:
        """'convolution' or 'dot' when the instruction is one or is a
        fusion around one, else ''."""
        ops = {instr.opcode}
        if instr.calls:
            ops |= self.computation_opcodes.get(instr.calls, set())
        for op in ("convolution", "dot"):
            if op in ops:
                return op
        return ""

    def kernel_kind(self, instr: Instr) -> str:
        """Which attention kernel a Mosaic custom call is. The program
        names no kernel (``name=`` on ``pallas_call`` is a need listed in
        PERF.md), so: the jitted wrapper in its ``op_name``
        (``jit(_flash_fwd)``, ``jit(_flash_bwd)``; both dispatch to the
        whole-K or the K-blocked kernels inside, which the HLO cannot tell
        apart) and, for the backward, what the call returns: the dq kernel
        one rank-4 array, the dk/dv kernel three (dk, dv, dbias), the
        fused one-pass backward four (dq, dk, dv, dbias)."""
        m = re.search(r"jit\((_flash_\w+)\)", instr.op_name) or \
            re.match(r"(_flash_\w+?)(?:\.\d+)?$", instr.name)
        if not m:
            return ""
        wrapper = m.group(1)
        if wrapper == "_flash_bwd":
            rank4 = sum(1 for s in instr.result_shapes if len(s[1]) == 4)
            part = {1: "dq", 3: "dkv", 4: "fused"}.get(rank4, f"{rank4}out")
            return f"{wrapper}:{part}"
        return wrapper

    def category(self, instr: Instr | None, event_name: str = "") -> str:
        if instr is None:
            base = event_name.strip().lstrip("%")
            if base.startswith(COLLECTIVE_OPCODES):
                return "collective"
            return "other"
        if instr.opcode.startswith(COLLECTIVE_OPCODES):
            return "collective"
        if instr.target == "tpu_custom_call":
            return "attn_kernel" if self.kernel_kind(instr) else "other"
        if "/optimizer_update/" in instr.op_name + "/":
            return "optimizer_update"
        if self.holds_matmul(instr):
            return "gemm_conv"
        return "other"

    def label(self, instr: Instr | None, event_name: str = "") -> str:
        """Short, stable name for the breakdown: category, then the scope
        with layer and block numbers folded."""
        cat = self.category(instr, event_name)
        if instr is None:
            return f"{cat}:{re.sub(r'[.\d]+$', '', event_name.strip('%'))}"
        if cat == "attn_kernel":
            return f"attn_kernel:{self.kernel_kind(instr)}"
        if cat == "collective":
            return f"collective:{instr.opcode}"
        if cat == "optimizer_update":
            return "optimizer_update"
        what = self.holds_matmul(instr) or instr.opcode
        return f"{what}:{scope_of(instr.op_name)}"


def scope_of(op_name: str) -> str:
    """``jit(_train_step_jit)/transpose(jvp(BertForMLM))/layer3/mlp_in/dot_general``
    -> ``bwd/layerN/mlp_in``."""
    parts = [p for p in op_name.split("/") if p]
    phase, path = "", []
    for p in parts:
        if p.startswith("transpose("):
            phase = "bwd"
        elif p.startswith("jvp(") and not phase:
            phase = "fwd"
        elif re.match(r"^(jit|pjit|checkpoint|remat|custom_vjp|custom_jvp|"
                      r"shard_map|vmap|while|cond|body|closed_call)\b", p) \
                or "(" in p:
            continue
        else:
            path.append(re.sub(r"\d+", "N", p))
    path = path[:-1] if len(path) > 1 else path   # drop the primitive's name
    return "/".join(([phase] if phase else []) + path[:3]) or "-"
