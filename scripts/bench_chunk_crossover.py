"""Ring-chunk implementation crossover microbench (real TPU).

Times fwd+bwd of one ring chunk — Pallas flash kernel vs the plain-XLA
chain — across chunk lengths, to (re)calibrate FLASH_CHUNK_MIN in
parallel/ring.py. Round 3 measured the crossover at 2048 with
f32-upcast kernel dots; the round-4 input-dtype kernels run ~2x faster,
so the constant must be re-derived, not trusted (PERF_NOTES.md).

Usage (serial with nothing else on the host — see the verify skill):

    python scripts/bench_chunk_crossover.py [chunk ...]

Prints one line per (chunk, impl): median fwd+bwd wall ms over ``reps``
timed calls after a warmup, each synced by fetching a scalar value. On a
CPU backend (``JAX_PLATFORMS=cpu``) the plumbing runs and the timings
mean nothing.
"""

import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.ops import flash_attention as _fa
from distributed_tensorflow_framework_tpu.parallel import ring

B, H, D = 4, 12, 64
REPS = 12


def time_impl(c: int, use_flash: bool) -> float:
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, c, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, c, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, c, H, D), jnp.bfloat16)
    bias = jnp.zeros((B, c), jnp.float32)

    def chunk(q, k, v, bias):
        # Time the PRODUCTION dispatch arms, not a copy: force
        # ring._chunk_attention down one arm by pinning its module-level
        # crossover (the documented force-path hook, cf.
        # tests/test_packed_attention.py). Trace-time mutation is safe —
        # each jit below traces exactly once, under its own pin.
        saved = ring.FLASH_CHUNK_MIN
        ring.FLASH_CHUNK_MIN = 0 if use_flash else 10**9
        try:
            out = ring._chunk_attention(q, k, v, bias)
        finally:
            ring.FLASH_CHUNK_MIN = saved
        return out

    @jax.jit
    def fwd_bwd(q, k, v, bias):
        def loss(q, k, v, bias):
            o, lse = chunk(q, k, v, bias)
            return (jnp.sum(o.astype(jnp.float32) ** 2)
                    + jnp.sum(lse.astype(jnp.float32)))

        val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, bias)
        return val + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

    float(fwd_bwd(q, k, v, bias))  # compile + warmup, synced by value fetch
    samples = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(fwd_bwd(q, k, v, bias))
        samples.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(samples)


def main() -> None:
    chunks = [int(a) for a in sys.argv[1:]] or [256, 512, 1024, 2048, 4096]
    print(f"chunk fwd+bwd median ms (B={B} H={H} D={D}, reps={REPS}), "
          f"dispatch FLASH_CHUNK_MIN={ring.FLASH_CHUNK_MIN}")
    for c in chunks:
        # Above MAX_SEQ_VMEM the dispatch routes to the flash kernels even
        # with FLASH_CHUNK_MIN pinned high (ring._chunk_attention's
        # `c > MAX_SEQ_VMEM` clause), so an "xla" timing there would
        # silently be a flash timing — and honestly forcing the XLA chain
        # would materialize a c x c f32 score block (12.9 GB at 8192).
        # Refuse instead (ADVICE r4).
        if c > _fa.MAX_SEQ_VMEM:
            if not _fa.chunk_supported(c):
                print(f"chunk {c:5d}: skipped — exceeds "
                      f"MAX_SEQ_VMEM={_fa.MAX_SEQ_VMEM} but is not a "
                      f"BLOCK_Q multiple, so neither arm can take it")
                continue
            flash_ms = time_impl(c, use_flash=True)
            print(f"chunk {c:5d}: xla      n/a ms   flash {flash_ms:8.2f} ms"
                  f"   -> flash (xla arm refused: chunk > "
                  f"MAX_SEQ_VMEM={_fa.MAX_SEQ_VMEM} would materialize a "
                  f"{c}x{c} score block)")
            continue
        xla_ms = time_impl(c, use_flash=False)
        flash_ms = time_impl(c, use_flash=True)
        winner = "flash" if flash_ms < xla_ms else "xla"
        print(f"chunk {c:5d}: xla {xla_ms:8.2f} ms   flash {flash_ms:8.2f} ms"
              f"   -> {winner}")


if __name__ == "__main__":
    main()
