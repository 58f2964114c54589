"""Test harness: 8 virtual CPU devices.

SURVEY.md §4 "Multi-replica without hardware": the TPU analogue of the
reference's fake-cluster-on-localhost trick is
``--xla_force_host_platform_device_count=8`` — real psum/shard_map/pjit
semantics, no TPU required. Env vars MUST be set before jax initializes,
hence this module-level block.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compilation cache under test (JAX's own switch, inherited
# by every subprocess a test spawns): the entry points would otherwise
# place one at <checkout>/.jax_cache (core/platform.py), and a suite whose
# compiles depend on what an earlier run left behind is not the same
# suite twice. tests/test_platform_cache.py covers the placement itself.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Rendezvous-timeout defaults: on a 1-core box a scheduling stall would
# otherwise abort multi-device collectives — see core/platform.py.
from distributed_tensorflow_framework_tpu.core.platform import (  # noqa: E402
    with_cpu_collective_timeouts,
)

os.environ["XLA_FLAGS"] = with_cpu_collective_timeouts(_flags)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def pin_whole_k_rows(monkeypatch):
    """``pin(rows, s)`` for tests parametrised over ``rows``: ``"128-row"``
    holds the whole-K flash forward to the 128-row blocks it shipped
    with (several blocks a head, and several row blocks' visits to one
    key block in the backward), which ``select_dispatch`` no longer
    picks at short lengths; ``"selected"`` leaves its choice alone.
    Either way it checks that an f32 sequence of ``s`` then runs the
    whole-K forward, and the backward on the streaming tile."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    def pin(rows: str, s: int) -> None:
        if rows == "128-row":
            monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
        picked = fa.select_dispatch(s, s, "float32")
        want = 128 if rows == "128-row" else s
        assert (picked.family, picked.block_q) == ("whole_k", want)
        assert (picked.bwd_block_q, picked.bwd_block_k) == (want, s)

    return pin


@pytest.fixture(scope="session")
def gang_capability():
    """Gate for tests that need a REAL multi-process jax.distributed gang.

    Stock CPU jaxlib forms the gang (coordinator handshake + global
    device discovery succeed) but rejects any computation spanning
    processes at compile time ("Multiprocess computations aren't
    implemented on the CPU backend"), so every end-to-end gang test
    would fail identically.  Probe once per session and SKIP those
    tests with the probe's evidence — the supervisor/launcher decision
    logic stays covered by the stubbed fast tiers (tests/test_cluster.py,
    tests/test_local_cluster_launcher.py).
    """
    from distributed_tensorflow_framework_tpu.core import cluster

    ok, detail = cluster.probe_gang(procs=2, devices_per_proc=2)
    if not ok:
        reason = ("backend cannot run real multi-process gangs"
                  if cluster.is_gang_unsupported(detail)
                  else "gang probe failed")
        pytest.skip(f"{reason}:\n{detail[-800:]}")


def write_imagenet_records(root, *, split="train", counts=(8, 8),
                           size=(64, 48), label_fn=None):
    """The ONE fabricated ImageNet-layout TFRecord writer for the suite
    (JPEG bytes + 1-based labels; shard naming `<split>-NNNNN-of-NNNNN`).
    ``counts`` gives records per shard file; ``label_fn`` maps the global
    1-based record counter to a label (default: identity-ish n%1000+1).
    Previously three near-identical writers had drifted across test
    files — record-format changes now have a single home."""
    import os

    import numpy as np
    import tensorflow as tf

    label_fn = label_fn or (lambda n: (n % 1000) + 1)
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(0)
    n = 0
    files = len(counts)
    for f, per_file in enumerate(counts):
        path = os.path.join(str(root), f"{split}-{f:05d}-of-{files:05d}")
        with tf.io.TFRecordWriter(path) as w:
            for _ in range(per_file):
                img = rng.integers(0, 255, (*size, 3), dtype=np.uint8)
                encoded = tf.io.encode_jpeg(img).numpy()
                n += 1
                ex = tf.train.Example(features=tf.train.Features(feature={
                    "image/encoded": tf.train.Feature(
                        bytes_list=tf.train.BytesList(value=[encoded])),
                    "image/class/label": tf.train.Feature(
                        int64_list=tf.train.Int64List(value=[label_fn(n)])),
                }))
                w.write(ex.SerializeToString())
