"""The MLM head and its loss on the labelled positions only (PR 29).

In the ``mlm`` training step the vocabulary projection, the softmax
cross-entropy, the argmax and their backward run on a window of each
row's positions, labelled first (models/bert.head_in_windows), and on
further windows only while some row has labels left. Held here, in
float32 at small widths: the windowed path equals today's ``MLMHead`` +
``mlm_loss`` on full logits for every count from none to all; the counter
says how many windows ran; the width comes from ``data.mask_prob``, the
task and the mesh; every other caller still gets (B, S, V) logits; the
step's update under grad accumulation and its collectives under ``jit``
over eight devices are what the whole-row program's are.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
from distributed_tensorflow_framework_tpu.data.infeed import to_global
from distributed_tensorflow_framework_tpu.models import bert
from distributed_tensorflow_framework_tpu.models.bert import (
    BertForMLM, LabelledWindows, head_in_windows, head_window)
from distributed_tensorflow_framework_tpu.train import losses
from distributed_tensorflow_framework_tpu.train.step import StepBuilder

B, S, P, V, H = 4, 16, 4, 50, 32


def _counts_to_targets(counts, seed=0):
    """(B, S) targets with ``counts[b]`` labels in row b, scattered."""
    rng = np.random.default_rng(seed)
    targets = np.full((len(counts), S), -1, np.int32)
    for b, c in enumerate(counts):
        at = rng.choice(S, size=c, replace=False)
        targets[b, at] = rng.integers(0, V, size=c)
    return jnp.asarray(targets)


# name -> (labels per row, windows the step must compute)
COUNTS = {
    "none": ((0, 0, 0, 0), 1),
    "under_p": ((1, 3, 2, 0), 1),
    "one_row_at_p": ((P, 1, 2, 3), 1),
    "one_row_at_p_plus_1": ((2, P + 1, 0, 3), 2),
    "every_position": ((S, S, S, S), math.ceil(S / P)),
    "unequal": ((0, 3 * P - 1, 1, S), math.ceil(S / P)),
    "three_windows": ((2 * P + 1, 0, P, 1), 3),
}


@pytest.fixture(scope="module")
def model_and_params():
    model = BertForMLM(vocab_size=V, hidden_size=H, num_layers=1,
                       num_heads=2, mlp_dim=64, max_seq_len=S,
                       dropout_rate=0.0, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, V, (B, S)),
                      jnp.int32)
    params = model.init({"params": jax.random.key(0)}, ids,
                        train=False)["params"]
    # A trained head has a bias; zeros would hide a dropped bias gradient.
    params["head"]["mlm_bias"] = jnp.asarray(
        np.random.default_rng(2).normal(0, 0.5, V), jnp.float32)
    return model, params, ids


def _full(model, params, ids, targets):
    logits = model.apply({"params": params}, ids, train=False)
    return losses.mlm_loss(logits, targets)


def _windowed(model, params, ids, targets, width=P):
    out = model.apply({"params": params}, ids, train=False,
                      labelled=LabelledWindows(targets, width,
                                               losses.mlm_sums))
    loss, metrics = losses.mlm_loss_of_sums(out.loss_sum, out.others,
                                            targets)
    return loss, dict(metrics, mlm_head_windows=out.windows)


@pytest.mark.parametrize("case", sorted(COUNTS))
def test_windowed_head_equals_full_logits_for_every_count(
        model_and_params, case):
    """Loss, accuracy and every gradient (head parameters, the tied
    embedding, and through the hidden states every encoder parameter)
    within 2e-5 of the whole-row path, and the counter."""
    model, params, ids = model_and_params
    counts, windows = COUNTS[case]
    targets = _counts_to_targets(counts)
    (want, want_m), want_g = jax.value_and_grad(
        lambda p: _full(model, p, ids, targets), has_aux=True)(params)
    (got, got_m), got_g = jax.jit(jax.value_and_grad(
        lambda p: _windowed(model, p, ids, targets), has_aux=True))(params)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got_m["mlm_acc"], want_m["mlm_acc"],
                               rtol=2e-5, atol=1e-7)
    assert float(got_m["mlm_head_windows"]) == windows
    flat_want = jax.tree_util.tree_leaves_with_path(want_g)
    flat_got = jax.tree.leaves(got_g)
    assert len(flat_want) == len(flat_got)
    for (path, a), b in zip(flat_want, flat_got):
        np.testing.assert_allclose(
            b, a, rtol=2e-5, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    if sum(counts):
        assert float(jnp.abs(want_g["head"]["mlm_bias"]).max()) > 0


@pytest.mark.parametrize("case", ["under_p", "one_row_at_p_plus_1",
                                  "every_position"])
def test_the_gradient_into_the_hidden_states_is_the_full_paths(case):
    """The function itself, away from the model: the gradient that the
    gather's backward scatters into (B, S, H), and a row length that the
    window does not divide (the last window runs past the row)."""
    s, width = 14, 4
    rng = np.random.default_rng(3)
    hidden = jnp.asarray(rng.normal(size=(B, s, H)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(V, H)), jnp.float32)
    counts = {"under_p": (1, 0, 3, 2), "one_row_at_p_plus_1": (5, 0, 1, 4),
              "every_position": (s,) * 4}[case]
    targets = np.full((B, s), -1, np.int32)
    for b, c in enumerate(counts):
        targets[b, rng.choice(s, size=c, replace=False)] = rng.integers(
            0, V, size=c)
    targets = jnp.asarray(targets)

    def full(h, t):
        return losses.mlm_loss(h @ t.T, targets)[0]

    def windowed(h, t):
        out = head_in_windows(
            lambda table, rows, there: losses.mlm_sums(rows @ table.T, there),
            width, t, h, targets)
        return losses.mlm_loss_of_sums(out.loss_sum, out.others,
                                       targets)[0]

    want = jax.value_and_grad(full, argnums=(0, 1))(hidden, table)
    got = jax.jit(jax.value_and_grad(windowed, argnums=(0, 1)))(hidden, table)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-6)
    # Unlabelled positions get an exact zero, as the mask gave them.
    unlabelled = np.asarray(targets) < 0
    assert not np.asarray(got[1][0])[unlabelled].any()


def test_labelled_positions_come_first_and_in_order():
    targets = jnp.asarray([[-1, 7, -1, 3, 9, -1], [-1] * 6, [1, 2, 3, 4, 5, 6]],
                          jnp.int32)
    pos, there = bert._labelled_first(targets, 8)
    np.testing.assert_array_equal(
        pos, [[1, 3, 4, 0, 2, 5, 0, 0], [0, 1, 2, 3, 4, 5, 0, 0],
              [0, 1, 2, 3, 4, 5, 0, 0]])
    np.testing.assert_array_equal(
        there, [[7, 3, 9, -1, -1, -1, -1, -1], [-1] * 8,
                [1, 2, 3, 4, 5, 6, -1, -1]])


@pytest.mark.parametrize("seq_len,mask_prob,want", [
    (512, 0.15, 128),       # bert_s512: Binomial(501, .15) is 75 +- 8
    (8192, 0.15, 1536),     # bert_s8192: 1229 +- 32
    (2048, 0.15, 384),
    (512, 0.6, 384),        # 1.25 * .6 * 512 = 384 = S - 128: still a window
    (512, 0.61, 512),       # over S - 128: the whole row
    (512, 1.0, 512),
    (512, 0.0, 128),        # never under one lane tile
    (64, 0.15, 64),         # a row under one tile is its own window
    (16, 0.15, 16),
])
def test_the_width_rule(seq_len, mask_prob, want):
    assert head_window(seq_len, mask_prob) == want
    assert want == seq_len or want % 128 == 0


def _bert_cfg(seq_len=256, accum=1, mesh=None, rows=16, **model):
    return load_config(base={
        "name": "mlm-head-windows-test",
        "mesh": mesh or {"data": 8},
        # A vocabulary of 96 is a width nothing else in the model has.
        "model": {"name": "bert", "vocab_size": 96, "hidden_size": 32,
                  "num_layers": 1, "num_heads": 2, "mlp_dim": 64,
                  "max_seq_len": seq_len, "dtype": "float32",
                  "dropout_rate": 0.0, **model},
        "data": {"name": "synthetic_mlm", "vocab_size": 64,
                 "global_batch_size": rows, "seq_len": seq_len,
                 "mask_prob": 0.15},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.1},
        "train": {"total_steps": 2, "grad_accum_steps": accum},
    })


def test_who_gets_a_window_and_who_the_whole_row(devices):
    """The step sizes the window from the task, ``data.mask_prob``, S and
    the mesh; a decoder, a sharded sequence and a model without the
    ``labelled`` keyword keep the whole row (today's program)."""
    cfg = _bert_cfg()
    assert StepBuilder(cfg, create_mesh(cfg.mesh)).mlm_head_window(256) == 128
    assert StepBuilder(cfg, create_mesh(cfg.mesh)).mlm_head_window(8192) == 1536
    assert StepBuilder(cfg, create_mesh(cfg.mesh)).mlm_head_window(96) == 96

    seq = _bert_cfg(mesh={"data": 4, "seq": 2}, attention_impl="ring")
    assert StepBuilder(seq, create_mesh(seq.mesh)).mlm_head_window(256) == 256

    pipe = _bert_cfg(mesh={"data": 2, "pipe": 4}, num_layers=4,
                     pipeline_stages=4)
    assert StepBuilder(pipe, create_mesh(pipe.mesh)).mlm_head_window(256) == 256

    lm = load_config(base={
        "name": "lm", "mesh": {"data": 8},
        "model": {"name": "lfm2", "vocab_size": 64, "hidden_size": 32,
                  "num_layers": 2, "layer_types": ["conv", "full_attention"],
                  "num_dense_layers": 2, "num_heads": 2, "num_kv_heads": 1, "mlp_dim": 64,
                  "max_seq_len": 256, "dtype": "float32"},
        "data": {"name": "synthetic_lm", "vocab_size": 64,
                 "global_batch_size": 8, "seq_len": 256, "mask_prob": 0.15},
        "train": {"total_steps": 1},
    })
    builder = StepBuilder(lm, create_mesh(lm.mesh))
    assert builder.task == "causal_lm"
    assert builder.mlm_head_window(256) == 256


def _batch(cfg, mesh, seed=0, counts=None):
    rng = np.random.default_rng(seed)
    b, s = cfg.data.global_batch_size, cfg.data.seq_len
    ids = rng.integers(4, 64, (b, s)).astype(np.int32)
    labelled = rng.random((b, s)) < 0.15
    if counts is not None:
        labelled = np.arange(s)[None, :] < np.asarray(counts)[:, None]
    targets = np.where(labelled, ids, -1).astype(np.int32)
    host = {"input_ids": np.where(labelled, 3, ids).astype(np.int32),
            "attention_mask": np.ones((b, s), np.int32),
            "targets": targets}
    return to_global(host, mesh)


def _whole_row(builder):
    """The same builder held to today's program: a window of the row."""
    builder.mlm_head_window = lambda seq_len: seq_len
    return builder


def _step(cfg, batch_of, whole_row=False):
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    if whole_row:
        _whole_row(builder)
    batch = batch_of(cfg, mesh)
    state = builder.init_state(0, batch)
    state, metrics = builder.make_train_step(batch)(state, batch)
    return jax.device_get(state.params), jax.device_get(metrics)


@pytest.mark.parametrize("counts,windows", [
    (None, 1),                                   # 15% by independent draws
    ([129] + [20] * 15, 2),                      # one row one over P
    ([256] * 16, 2),                             # every position
])
def test_the_train_step_takes_the_windowed_path_and_updates_alike(
        devices, counts, windows):
    cfg = _bert_cfg()
    batch_of = lambda c, m: _batch(c, m, counts=counts)  # noqa: E731
    p_w, m_w = _step(cfg, batch_of)
    p_f, m_f = _step(cfg, batch_of, whole_row=True)
    assert float(m_w["mlm_head_windows"]) == windows
    assert "mlm_head_windows" not in m_f
    for name in ("loss", "mlm_acc", "grad_norm"):
        np.testing.assert_allclose(m_w[name], m_f[name], rtol=2e-5, atol=1e-7)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p_f),
                            jax.tree.leaves(p_w)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_grad_accumulation_weights_the_windowed_microbatches_alike(devices):
    """Microbatches hold unequal label counts; the accumulated update
    (weighted by each one's count, train/step.py _microbatch_weight) is
    the one batch's, with the windowed head inside the scan."""
    counts = [130] * 8 + [5] * 8 + [40] * 16     # microbatches of 8 rows
    batch_of = lambda c, m: _batch(c, m, counts=counts)  # noqa: E731
    p1, m1 = _step(_bert_cfg(rows=32), batch_of)
    p4, m4 = _step(_bert_cfg(rows=32, accum=4), batch_of)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(b, a, rtol=5e-5, atol=5e-6)
    np.testing.assert_allclose(m4["loss"], m1["loss"], rtol=1e-4)
    assert float(m1["mlm_head_windows"]) == 2
    # Two windows in the first microbatch, one in the others, weighted by
    # the labels each holds.
    want = (2 * 1040 + 1 * 40 + 1 * 320 + 1 * 320) / 1720
    np.testing.assert_allclose(m4["mlm_head_windows"], want, rtol=1e-5)


_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_ARRAY = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


def _collectives(cfg, whole_row):
    """Every array that a collective of the compiled step moves, as
    (opcode, dtype, dims): those of the entry computation, which every
    step runs, apart from those of the others (the loops' bodies); and
    the step's text."""
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    if whole_row:
        _whole_row(builder)
    batch = _batch(cfg, mesh)
    state = builder.init_state(0, batch)
    text = builder.make_train_step(batch).lower(state, batch).compile(
        ).as_text()
    entry = text.index("\nENTRY ")
    moved = {True: [], False: []}
    for m in _COLLECTIVE.finditer(text):
        result, op = m.groups()
        moved[m.start() > entry] += [
            (op, dtype, dims) for dtype, dims in _ARRAY.findall(result)]
    return sorted(moved[True]), sorted(moved[False]), text


def test_under_jit_over_eight_devices_the_rows_stay_on_their_chip(devices):
    """``spmd_mode=jit``, batch sharded over ``mesh.data=8``: the rows are
    taken along S with B a batch dimension, so what every step runs holds
    the whole-row program's collectives and one more, the all-reduce of
    one integer (the fullest row's count: every chip must take the same
    number of windows). The loops for the further windows reduce their
    sums and the head's parameter gradients themselves, when they run.
    No all-gather, nothing of the batch's size moved."""
    cfg = _bert_cfg()
    got, looped, text = _collectives(cfg, whole_row=False)
    want, nothing, _ = _collectives(cfg, whole_row=True)
    assert len(want) > 10 and nothing == []
    extra = list(got)
    for moved in want:
        extra.remove(moved)
    assert extra == [("all-reduce", "s32", "")]
    assert {op for op, _, _ in looped} == {"all-reduce"}
    head = {"", "32", "96", "32,32", "96,32"}    # sums, head parameters
    assert {dims for _, _, dims in looped} <= head, looped
    # Per device: 2 rows of 256; the head's logits are (2, 128, 96) and no
    # array of vocabulary width has the whole row's positions.
    assert re.search(r"f32\[2,128,96\]", text)
    assert not re.search(r"f32\[2,256,96\]", text)


def test_every_other_caller_still_gets_whole_row_logits(devices,
                                                        model_and_params):
    """The eval step, the serving forward and the pipelined stack's head
    take no window: (B, S, V), as before."""
    model, params, ids = model_and_params
    assert model.apply({"params": params}, ids, train=False).shape == (B, S, V)

    cfg = _bert_cfg()
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    batch = _batch(cfg, mesh)
    state = builder.init_state(0, batch)
    ev = builder.make_eval_step(batch)
    assert re.search(r"f32\[2,256,96\]",
                     ev.lower(state, batch).compile().as_text())
    sums = jax.device_get(ev(state, batch))
    _, m = _step(cfg, _batch)
    np.testing.assert_allclose(sums["loss_sum"] / sums["weight_sum"],
                               m["loss"], rtol=2e-5)

    from distributed_tensorflow_framework_tpu.serve.engine import (
        make_forward, serving_mesh)
    forward = make_forward(builder.model, serving_mesh(1))
    host = jax.device_get(batch)
    logits = forward({"params": jax.device_get(state.params)},
                     (host["input_ids"], host["attention_mask"]))
    assert logits.shape == (16, 256, 96)


def test_the_pipelined_heads_logits_keep_every_position(devices):
    from distributed_tensorflow_framework_tpu.parallel.pipeline import (
        PipelinedBert)

    mesh = create_mesh(load_config(base={
        "mesh": {"data": 2, "pipe": 4}}).mesh)
    model = PipelinedBert(vocab_size=V, hidden_size=H, num_layers=4,
                          num_heads=2, mlp_dim=64, max_seq_len=S,
                          dropout_rate=0.0, dtype=jnp.float32, mesh=mesh,
                          num_stages=4, num_microbatches=4)
    ids = jnp.zeros((8, S), jnp.int32)
    variables = model.init({"params": jax.random.key(0)}, ids)
    assert model.apply(variables, ids, train=False).shape == (8, S, V)
