"""On-DEVICE compile-and-agree check for every flash-attention kernel.

ops/flash_attention.py has five Pallas kernels: a whole-K and a
streaming forward, the fused one-pass streaming backward, and the
streaming two-pass pair. Which ones a call runs, and on what tile,
``select_dispatch`` decides from the sequence lengths, the bytes of the
input dtype and the platform (the module docstring has the rule, PERF.md
the measurements). A training step reaches only what its shapes select.
This drives ``value_and_grad`` through all of them, segmented and
unsegmented:

  case                seq   dtype  backward   what runs
  cell_s512           512   bf16   as chosen  the default path at ``bert_s512``'s
                                              shape: whole-K forward on 512 rows,
                                              fused one-pass backward (v5e)
  whole_k_short       512   bf16   two-pass   whole-K forward, streaming dq and
                                              dk/dv on one 512x512 tile: what a
                                              TPU off the verified list runs
  whole_k_max         4096  bf16   two-pass   whole-K forward at MAX_SEQ_VMEM on
                                              128-row blocks, two-pass pair on
                                              512x1024 tiles
  kblocked            8192  bf16   two-pass   streaming fwd/dq/dkv, 512x1024 tiles
  fused               8192  bf16   fused      streaming fwd + fused backward
                                              (``bert_s8192``)
  fused_takeover_min  128   bf16   fused      both ends and the middle of the
  fused_takeover      2048  bf16   fused      lengths that pair the whole-K
  fused_takeover_max  4096  bf16   fused      forward with the fused backward:
                                              BLOCK_Q..MAX_SEQ_VMEM
  f32_s128 .. s4096   128, 512, 2048, 4096    the same pairing on float32 inputs,
                            f32    as chosen  up to the longest the fused
                                              backward's byte gate lets through
  sub_tile            64    bf16   as chosen  a sequence under one tile: a key
  f32_sub_tile        64    f32    as chosen  tile that is not whole lanes takes
                                              the two-pass pair (Mosaic refuses
                                              the fused kernel there)
  causal_gqa_s512     512   bf16   as chosen  ``causal=True`` with one key/value
  causal_gqa_s8192    8192  bf16   as chosen  head per four query heads: the mask
                                              from indices, k/v through the block
                                              index maps, blocks above the diagonal
                                              or outside the document skipped;
                                              8192 is ``lfm2_moe_s8192``'s call
  window_d128_s4096   4096  bf16   as chosen  ``window=`` on top of that, heads of
  window_d128_s8192   8192  bf16   as chosen  128 dims, seven query heads a
  window_d128_s8192_fused  8192    fused      key/value head: a window of 1024 at
  window_d128_s16384  16384 bf16   as chosen  4096 keys (whole-K forward, fused
                                              backward), of 4096 at 8192 and
                                              16,384 (streaming kernels, blocks
                                              behind the window skipped; the
                                              two-pass pair by the scratch gate,
                                              and at 8192 the fused backward too,
                                              its gate raised for the case);
                                              16,384 is ``smallthinker_s16384``'s
                                              window layers' call
  window512_d128_s16384  16384  bf16  as chosen  a window of 512, narrower than the
                                              1024-key tile, nine query heads over
                                              one key/value head:
                                              ``laguna_s_s16384``'s window layers'
                                              call
  latent_d192_v128_s2048   2048  bf16  as chosen  ``causal=True`` with value heads
  latent_d192_v128_s16384  16384 bf16  as chosen  narrower than the query/key heads:
                                              16 heads of 192 over values of 128,
                                              a key/value head a query head; the
                                              fused backward at 2048, the
                                              streaming kernels and the two-pass
                                              pair at 16,384, ``kanana2_s16384``'s
                                              latent attention call

Every case is held to a float32 ``jax.numpy`` reference computed one
head at a time (so it fits at any length), and the fused backward is
additionally held to the two-pass backward at the same length: its
dk/dv/dbias rest on in-order HBM flushes of revisited output blocks — a
Mosaic behaviour that interpret mode, which walks the grid sequentially
by construction, cannot exercise. Run THIS before trusting the kernels on
a new backend or toolchain. A kernel the compiler refuses raises here;
nothing is routed around it.

    python scripts/verify_flash_kernels.py [case ...]

The last line of stdout is one JSON object: ``ok``, the ``platform``,
``device_kind`` and ``kernel_mode`` ("mosaic" | "interpret") it ran in,
which backward the default dispatch picks at the streaming length
(``streaming_backward_default``), and each case's statistics, the
dispatch it ran under among them. Exit 0 when every case agrees, 1
otherwise. The lengths follow the module's own thresholds, so the
FLASH_* variables shrink the matrix for a CPU plumbing run (timings and
flush order mean nothing there).
"""

import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_framework_tpu.core import platform
from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

B = int(os.environ.get("VFB_B", "1"))
H = int(os.environ.get("VFB_H", "12"))
D = int(os.environ.get("VFB_D", "64"))

# Relative-L2 gates. Against the f32 reference the kernels differ by bf16
# rounding of p/ds and of the outputs (1e-2 class at worst); a wrong tile,
# mask or flush order moves the error to the 1e0 class. Fused and two-pass
# backward share one accumulation order when both stream, so they agree
# far inside the same gate.
GATE_VS_REFERENCE = 3e-2
GATE_FUSED_VS_TWO_PASS = 3e-2


# The platform's own answer, kept so that a case can hand the choice back
# after another case has forced a backward through the module's one seam.
_PLATFORM_RULE = fa.fused_bwd_enabled


def _allow_fused(setting: bool | None) -> None:
    fa.fused_bwd_enabled = (_PLATFORM_RULE if setting is None
                            else lambda: setting)


def _cases() -> dict:
    """name -> (seq, whether the fused backward is allowed in the case:
    None leaves that to the platform, as a training run does; input
    dtype)."""
    vmem = fa.MAX_SEQ_VMEM
    bf16, f32 = jnp.bfloat16, jnp.float32
    cases = {
        "cell_s512": (max(vmem // 8, fa.BLOCK_Q), None, bf16),
        "whole_k_short": (max(vmem // 8, fa.BLOCK_Q), False, bf16),
        "whole_k_max": (vmem, False, bf16),
        "kblocked": (2 * vmem, False, bf16),
        "fused": (2 * vmem, True, bf16),
        "fused_takeover_min": (fa.BLOCK_Q, True, bf16),
        "fused_takeover": (max(vmem // 2, fa.BLOCK_Q), True, bf16),
        "fused_takeover_max": (vmem, True, bf16),
    }
    for seq in sorted({fa.BLOCK_Q, max(vmem // 8, fa.BLOCK_Q),
                       max(vmem // 2, fa.BLOCK_Q), vmem}):
        cases[f"f32_s{seq}"] = (seq, None, f32)
    cases["sub_tile"] = (fa.BLOCK_Q // 2, None, bf16)
    cases["f32_sub_tile"] = (fa.BLOCK_Q // 2, None, f32)
    return cases


# Query heads per key/value head in the causal cases.
KV_GROUP = 4


def _causal_cases() -> dict:
    """As ``_cases``: ``causal=True`` over grouped key/value heads, on
    the path the platform chooses, under both forwards."""
    vmem = fa.MAX_SEQ_VMEM
    return {
        "causal_gqa_s512": (max(vmem // 8, fa.BLOCK_Q), None, jnp.bfloat16),
        "causal_gqa_s8192": (2 * vmem, None, jnp.bfloat16),
    }


# The window cases' shapes: ``smallthinker_s16384``'s head size and group.
WINDOW_HEADS, WINDOW_KV_HEADS, WINDOW_D = 14, 2, 128
# ... and the cases with (query heads, key/value heads) of their own.
WINDOW_CASE_HEADS = {"window512_d128_s16384": (9, 1)}


def _window_cases() -> dict:
    """name -> (seq, fused allowed, dtype, window, FUSED_BWD_MAX for the
    case or None for the module's)."""
    vmem, bf16 = fa.MAX_SEQ_VMEM, jnp.bfloat16
    return {
        "window_d128_s4096": (vmem, None, bf16, vmem // 4, None),
        "window_d128_s8192": (2 * vmem, None, bf16, vmem, None),
        "window_d128_s8192_fused": (2 * vmem, True, bf16, vmem,
                                    4 * fa.FUSED_BWD_MAX),
        "window_d128_s16384": (4 * vmem, None, bf16, vmem, None),
        "window512_d128_s16384": (4 * vmem, None, bf16, 512, None),
    }


# The latent attention cases' (heads, query/key dims, value dims).
LATENT_HEADS, LATENT_D, LATENT_D_V = 16, 192, 128


def _latent_cases() -> dict:
    """As ``_cases``, causal over value heads of their own width."""
    vmem, bf16 = fa.MAX_SEQ_VMEM, jnp.bfloat16
    return {"latent_d192_v128_s2048": (max(vmem // 2, fa.BLOCK_Q), None, bf16),
            "latent_d192_v128_s16384": (4 * vmem, None, bf16)}


def _inputs(seq: int, kv_heads: int, dtype, heads: int = H, d: int = D,
            d_v: int | None = None):
    kq, kk, kv = jax.random.split(jax.random.key(seq), 3)
    q, k, v = (jax.random.normal(r, (B, seq, n, w), dtype)
               for r, n, w in ((kq, heads, d), (kk, kv_heads, d),
                               (kv, kv_heads, d_v or d)))
    # Four packed documents of unequal length per row.
    cuts = np.array([0.15, 0.4, 0.8]) * seq
    seg = np.searchsorted(cuts, np.arange(seq), side="right") + 1
    return q, k, v, jnp.asarray(np.tile(seg, (B, 1)), jnp.int32)


def _loss_and_out(out):
    return jnp.sum(jnp.sin(out.astype(jnp.float32))), out


def _kernel_fn(segmented: bool, causal: bool = False, window=None):
    def loss(q, k, v, seg):
        return _loss_and_out(fa.flash_attention(
            q, k, v, segment_ids=seg if segmented else None, causal=causal,
            window=window))
    return loss


def _reference_fn(segmented: bool, causal: bool = False, window=None):
    """float32 attention, one (batch, head) at a time under ``lax.map`` —
    an (S, S) score block per step, never (B, H, S, S). Grouped key/value
    heads are repeated for their query heads (the gradient sums back)."""
    def one_head(args):
        q, k, v, seg = args                       # (S, D) f32, (S,) int
        s = (q @ k.T) / jnp.sqrt(jnp.float32(q.shape[-1]))
        if segmented:
            s = jnp.where(seg[:, None] == seg[None, :], s, fa.NEG_INF)
        if causal:
            at = jnp.arange(s.shape[0])
            s = jnp.where(at[:, None] >= at[None, :], s, fa.NEG_INF)
            if window is not None:
                s = jnp.where(at[:, None] - at[None, :] < window, s,
                              fa.NEG_INF)
        return jax.nn.softmax(s, axis=-1) @ v

    def loss(q, k, v, seg):
        b, s, h, d = q.shape
        group = h // k.shape[2]
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        qf, kf, vf = (t.astype(jnp.float32).transpose(0, 2, 1, 3)
                      .reshape(b * h, s, t.shape[-1]) for t in (q, k, v))
        segf = jnp.repeat(seg, h, axis=0)         # (B*H, S)
        # Under jax.checkpoint: the backward keeps no (S, S) block of a
        # head but the one it works on (14 of them are 15 GB at 16,384).
        out = jax.lax.map(jax.checkpoint(one_head), (qf, kf, vf, segf))
        # Output in the kernels' dtype, so the loss sees the same values.
        out = out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)
        return _loss_and_out(out.astype(q.dtype))
    return loss


def _run(loss_fn, args) -> tuple[list, int]:
    """([out, dq, dk, dv] as float32 numpy arrays, Mosaic custom calls in
    the lowered program — 0 in interpret mode)."""
    lowered = jax.jit(jax.value_and_grad(
        loss_fn, argnums=(0, 1, 2), has_aux=True)).lower(*args)
    (_, out), grads = lowered.compile()(*args)
    jax.block_until_ready((out, grads))
    return ([np.asarray(t, np.float32) for t in (out, *grads)],
            lowered.as_text().count("tpu_custom_call"))


def _rel_l2(a, b) -> float:
    return float(np.linalg.norm(a - b) / max(float(np.linalg.norm(b)), 1e-30))


def run_case(name: str, seq: int, setting: bool | None, dtype,
             two_pass_cache: dict, causal: bool = False, window=None,
             latent: bool = False) -> dict:
    if latent:
        args = _inputs(seq, LATENT_HEADS, dtype, LATENT_HEADS, LATENT_D,
                       LATENT_D_V)
    elif window is None:
        args = _inputs(seq, H // KV_GROUP if causal else H, dtype)
    else:
        heads, kv_heads = WINDOW_CASE_HEADS.get(
            name, (WINDOW_HEADS, WINDOW_KV_HEADS))
        args = _inputs(seq, kv_heads, dtype, heads, WINDOW_D)
    head_dim, v_head_dim = int(args[0].shape[-1]), int(args[2].shape[-1])
    _allow_fused(setting)
    dispatch = fa.select_dispatch(seq, seq, dtype, head_dim, v_head_dim)
    fused = dispatch.backward == "fused"
    dtype_name = jnp.dtype(dtype).name
    rec = {"case": name, "seq": seq, "dtype": dtype_name,
           "fused_bwd": fused, "causal": causal, "window": window,
           "head_dim": head_dim, "v_head_dim": v_head_dim,
           "kv_heads": int(args[1].shape[2]),
           "dispatch": dispatch._asdict(), "variants": {}}
    key = (seq, dtype_name, causal, window, head_dim, v_head_dim,
           int(args[0].shape[2]))
    ok = True
    for segmented in (False, True):
        _allow_fused(setting)
        # Fresh outer trace per setting: the fused decision is read at
        # the custom_vjp layer, outside the inner jit's cache.
        got, mosaic_calls = _run(_kernel_fn(segmented, causal, window), args)
        want, _ = _run(_reference_fn(segmented, causal, window), args)
        stats = {
            "mosaic_calls": mosaic_calls,
            "finite": bool(all(np.isfinite(t).all() for t in got)),
        }
        for n, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            stats[f"{n}_rel_l2_vs_reference"] = _rel_l2(g, w)
        good = stats["finite"] and max(
            v for k, v in stats.items() if k.endswith("_vs_reference")
        ) <= GATE_VS_REFERENCE
        if fused:
            two_pass = two_pass_cache.get((*key, segmented))
            if two_pass is None:
                _allow_fused(False)
                two_pass, _ = _run(_kernel_fn(segmented, causal, window),
                                   args)
            diff = max(_rel_l2(g, t) for g, t in zip(got, two_pass))
            stats["rel_l2_vs_two_pass"] = diff
            good = good and diff <= GATE_FUSED_VS_TWO_PASS
        else:
            two_pass_cache[(*key, segmented)] = got
        stats["ok"] = bool(good)
        ok = ok and good
        rec["variants"]["segmented" if segmented else "unsegmented"] = stats
        print(f"{name} seq {seq} {dtype_name} "
              f"{'seg' if segmented else 'unseg'}: "
              + " ".join(f"{k}={v:.3e}" if isinstance(v, float) else f"{k}={v}"
                         for k, v in stats.items()), flush=True)
    rec["ok"] = ok
    return rec


def main(argv) -> int:
    causal_cases, window_cases = _causal_cases(), _window_cases()
    latent_cases = _latent_cases()
    cases = {**_cases(), **causal_cases, **window_cases, **latent_cases}
    unknown = [a for a in argv if a not in cases]
    if unknown:
        print(f"unknown case(s) {unknown}; known: {sorted(cases)}",
              file=sys.stderr)
        return 2
    selected = argv or list(cases)
    platform.resolve_compilation_cache()
    dev = jax.devices()[0]
    stream_seq = 2 * fa.MAX_SEQ_VMEM
    streaming_default = fa.select_dispatch(
        stream_seq, stream_seq, jnp.bfloat16).backward
    print(f"flash kernels on {dev.platform} ({dev.device_kind}), "
          f"{fa.kernel_mode()} mode, B={B} H={H} D={D}; default bf16 "
          f"backward at seq {stream_seq}: {streaming_default}", flush=True)
    two_pass_cache: dict = {}
    results = []
    for name in selected:
        if name in window_cases:
            seq, setting, dtype, window, fused_max = window_cases[name]
            module_max = fa.FUSED_BWD_MAX
            fa.FUSED_BWD_MAX = fused_max or module_max
            try:
                results.append(run_case(name, seq, setting, dtype,
                                        two_pass_cache, causal=True,
                                        window=window))
            finally:
                fa.FUSED_BWD_MAX = module_max
        else:
            results.append(run_case(
                name, *cases[name], two_pass_cache,
                causal=name in causal_cases or name in latent_cases,
                latent=name in latent_cases))
    ok = all(r["ok"] for r in results)
    if not ok:
        print("FLASH KERNEL MISMATCH — do not trust these kernels on this "
              "backend/toolchain; where the fused backward disagrees the "
              "flush ordering is suspect (take the generation off "
              "FUSED_BWD_VERIFIED_PLATFORMS)", flush=True)
    print(json.dumps({
        "ok": ok, "platform": dev.platform, "device_kind": dev.device_kind,
        "kernel_mode": fa.kernel_mode(),
        "streaming_backward_default": streaming_default,
        "cases": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
