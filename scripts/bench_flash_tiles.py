"""Kernel time per query-key pair for the flash-attention dispatch (real TPU).

Times the jitted ``_flash_fwd`` / ``_flash_bwd`` wrappers of
ops/flash_attention.py at one sequence length under the dispatch the
module selects AND under hand-built ones (another family, another tile,
the other backward), so a change to ``select_dispatch`` rests on a table
from the chip and not on an extrapolation. The numbers are device
durations from a profiler trace (the Mosaic calls alone, as the
benchmark's ``attn_kernel_pct`` counts them), with the host clock around
the same calls beside them as a cross-check.

    python scripts/bench_flash_tiles.py [--compile-only] [seq ...]

One line per (seq, pass, dispatch): calls, mean microseconds a call and
picoseconds a dense pair (rows × heads × seq²). The last stdout line is
one JSON object with every row, the device and the kernel mode.
``--compile-only`` compiles every variant for a described v5e in the
sandbox (no chip, no run, no number): what Mosaic refuses there it
refuses on the chip. Off the chip without that flag the plumbing runs in
interpret mode and the timings mean nothing.
"""

import argparse
import collections
import glob
import json
import os
import pathlib
import re
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

H, D = 12, 64
DTYPE = jnp.bfloat16      # the cells' input dtype
ROWS_AT_512 = 32          # bert_s512's per-chip batch; rows × seq held fixed
CALLS = 10
MOSAIC = re.compile(r"^_flash_(fwd|bwd)")


def variants(s: int) -> tuple[list, list]:
    """(forward dispatches, backward dispatches) worth timing at ``s``:
    the module's own selection first; then, forward, every whole-K row
    block the area allows and the streaming family; backward, the fused
    kernel and the two-pass pair on the streaming tile."""
    chosen = fa.select_dispatch(s, s, DTYPE)
    stream = (fa._pick_block(s, fa.BLOCK_Q_KB), fa._pick_block(s, fa.BLOCK_K_KB))
    rows = [r for r in (128, 256, 512)
            if r <= s and r * s <= fa.BLOCK_Q * fa.MAX_SEQ_VMEM]

    def make(family, bq, bk, backward="two_pass"):
        return fa.FlashDispatch(family, bq, bk, backward, *stream)

    fwd = [chosen] + [make("whole_k", r, s) for r in rows]
    fwd.append(make("stream", *stream))
    bwd = [chosen, make("stream", *stream, "fused"),
           make("stream", *stream, "two_pass")]
    return _unique(fwd, lambda d: d[:3]), _unique(bwd, lambda d: d[3:])


def _unique(items, key):
    seen, out = set(), []
    for item in items:
        if key(item) not in seen:
            seen.add(key(item))
            out.append(item)
    return out


def inputs(s: int, abstract_on=None):
    b = max(1, ROWS_AT_512 * 512 // s)
    shapes = {
        "q": ((b, H, s, D), DTYPE), "k": ((b, H, s, D), DTYPE),
        "v": ((b, H, s, D), DTYPE), "bias": ((b, 1, s), jnp.float32),
        "seg": ((b, 1, s), jnp.float32), "do": ((b, H, s, D), DTYPE),
        "o": ((b, H, s, D), DTYPE), "lse": ((b, H, s, 1), jnp.float32),
    }
    if abstract_on is not None:
        return b, {n: jax.ShapeDtypeStruct(sh, dt, sharding=abstract_on)
                   for n, (sh, dt) in shapes.items()}
    keys = jax.random.split(jax.random.key(s), 4)
    arrays = {n: jax.random.normal(kk, shapes[n][0], DTYPE)
              for n, kk in zip(("q", "k", "v", "do"), keys)}
    arrays["bias"] = jnp.zeros(shapes["bias"][0], jnp.float32)
    # Four packed documents of unequal length a row, as verify_flash_kernels.
    cuts = np.array([0.15, 0.4, 0.8]) * s
    seg = np.searchsorted(cuts, np.arange(s), side="right") + 1
    arrays["seg"] = jnp.asarray(np.tile(seg, (b, 1, 1)), jnp.float32)
    return b, arrays


def calls_for(a: dict, dispatch, segmented: bool, interpret: bool):
    """(forward thunk, backward thunk) over arrays or shape structs."""
    segs = (a["seg"], a["seg"]) if segmented else ()
    kw = dict(segmented=segmented, interpret=interpret, dispatch=dispatch)

    def forward(lower=False):
        f = fa._flash_fwd.lower if lower else fa._flash_fwd
        return f(a["q"], a["k"], a["v"], a["bias"], *segs, **kw)

    def backward(lower=False):
        f = fa._flash_bwd.lower if lower else fa._flash_bwd
        return f(a["q"], a["k"], a["v"], a["bias"], *segs,
                 a["o"], a["lse"], a["do"], **kw)

    return forward, backward


def device_ops(trace_dir: str) -> dict:
    """name (digits stripped) -> [calls, seconds] on device 0's ops line."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    ops: dict = collections.defaultdict(lambda: [0, 0.0])
    if not files:
        return ops
    profile = jax.profiler.ProfileData.from_file(files[-1])
    for plane in profile.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = re.sub(r"[.\d]+$", "", ev.name.lstrip("%").split(" ")[0])
                ops[name][0] += 1
                ops[name][1] += ev.duration_ns * 1e-9
    return ops


def time_one(thunk) -> dict:
    jax.block_until_ready(thunk())                   # compile + warm
    jax.block_until_ready(thunk())
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = thunk()
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / CALLS
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(CALLS):
            out = thunk()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        ops = device_ops(tmp)
    mosaic = {n: v for n, v in ops.items() if MOSAIC.match(n)}
    kernel_s = sum(v[1] for v in mosaic.values()) / CALLS
    return {"wall_us": wall * 1e6, "kernel_us": kernel_s * 1e6,
            "mosaic_calls": sum(v[0] for v in mosaic.values()) // CALLS,
            "other_us": (sum(v[1] for v in ops.values()) / CALLS - kernel_s)
            * 1e6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("seqs", nargs="*", type=int, default=[512])
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--unsegmented", action="store_true")
    args = ap.parse_args(argv)
    segmented = not args.unsegmented
    abstract_on = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        abstract_on = SingleDeviceSharding(topo.devices[0])
    dev = jax.devices()[0]
    interpret = fa._interpret() and not args.compile_only
    print(f"flash tiles on {dev.platform} ({dev.device_kind}), "
          f"{'compile only for v5e' if args.compile_only else fa.kernel_mode()}"
          f", H={H} D={D} {jnp.dtype(DTYPE).name} "
          f"{'segmented' if segmented else 'unsegmented'}",
          flush=True)
    rows = []
    for s in args.seqs:
        b, a = inputs(s, abstract_on)
        pairs = b * H * s * s
        fwds, bwds = variants(s)
        if abstract_on is None:
            fwd0, _ = calls_for(a, fwds[0], segmented, interpret)
            a["o"], a["lse"] = fwd0()
        for which, dispatches in (("fwd", fwds), ("bwd", bwds)):
            for i, d in enumerate(dispatches):
                part = d[:3] if which == "fwd" else d[3:]
                label = " ".join(str(x) for x in part)
                thunk = calls_for(a, d, segmented, interpret)[which == "bwd"]
                row = {"seq": s, "rows": b, "pass": which, "dispatch": label,
                       "selected": i == 0}
                try:
                    if args.compile_only:
                        thunk(lower=True).compile()
                        row["compiled"] = True
                    else:
                        row.update(time_one(thunk))
                        row["ps_per_pair"] = row["kernel_us"] * 1e6 / pairs
                except Exception as e:  # a refusal is a finding, keep going
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "kernel_mode": fa.kernel_mode(),
                      "dtype": jnp.dtype(DTYPE).name,
                      "compile_only": args.compile_only, "rows": rows}))
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
