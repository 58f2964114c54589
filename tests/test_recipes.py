"""The shipped recipes: every ``configs/*.yaml`` loads, validates and
names a model the registry builds.

Two cases per recipe. The first loads the file through ``load_config``
(which validates it) and resolves its model through ``get_model``. The
second builds that model abstractly (``jax.eval_shape`` of its init and
one forward) at the recipe's own widths; a recipe with a layer count is
cut to its first two layers, so a case takes seconds on the CPU.
"""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
from distributed_tensorflow_framework_tpu.models import get_model
from distributed_tensorflow_framework_tpu.train.step import (
    model_inputs, task_for_model)

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
RECIPES = sorted(os.path.basename(p)[:-len(".yaml")]
                 for p in glob.glob(os.path.join(_CONFIGS, "*.yaml")))
# The data source each task trains on: a recipe whose model and data
# disagree would fail only at its first step.
_TASK_DATA = {"classification": {"mnist", "cifar10", "imagenet"},
              "mlm": {"text_mlm"}, "causal_lm": {"synthetic_lm"}}


def _path(name):
    return os.path.join(_CONFIGS, f"{name}.yaml")


def _two_layers(cfg):
    """Overrides that keep a transformer recipe's first two layers: its
    per-layer lists cut to match, and at most one dense layer, so that a
    mixture recipe still builds one of each. The image models have no
    layer count."""
    m = cfg.model
    if task_for_model(m.name) == "classification":
        return []
    cut = ["model.num_layers=2",
           f"model.num_dense_layers={min(m.num_dense_layers, 1)}"]
    if m.layer_types:
        cut.append(f"model.layer_types=[{','.join(m.layer_types[:2])}]")
    if m.rope_layout:
        rope = ",".join(str(r) for r in m.rope_layout[:2])
        cut.append(f"model.rope_layout=[{rope}]")
    return cut


def _sample(cfg, task):
    """One abstract batch of the recipe's own shapes."""
    d = cfg.data
    if task == "classification":
        return {"image": jax.ShapeDtypeStruct(
            (8, d.image_size, d.image_size, d.channels), jnp.float32)}
    names = (("input_ids", "attention_mask") if task == "mlm"
             else ("input_ids",))
    return {k: jax.ShapeDtypeStruct((8, d.seq_len), jnp.int32)
            for k in names}


@pytest.mark.parametrize("name", RECIPES)
def test_recipe_loads_and_names_a_registered_model(devices, name):
    cfg = load_config(_path(name))
    task = task_for_model(cfg.model.name)
    assert cfg.data.name in _TASK_DATA[task], (task, cfg.data.name)
    model = get_model(cfg.model, mesh=create_mesh(cfg.mesh),
                      precision=cfg.precision)
    assert callable(getattr(model, "init", None))


@pytest.mark.parametrize("name", RECIPES)
def test_recipe_model_builds_at_its_widths(devices, name):
    shipped = load_config(_path(name))
    cfg = load_config(_path(name), overrides=_two_layers(shipped))
    task = task_for_model(cfg.model.name)
    model = get_model(cfg.model, mesh=create_mesh(cfg.mesh),
                      precision=cfg.precision)
    inputs = model_inputs(task, _sample(cfg, task))

    def build(*xs):
        rngs = {"params": jax.random.key(0), "dropout": jax.random.key(1)}
        variables = model.init(rngs, *xs, train=False)
        return variables, model.apply(variables, *xs, train=False)

    variables, out = jax.eval_shape(build, *inputs)
    logits = out["logits"] if isinstance(out, dict) else out
    m = cfg.model
    if task == "classification":
        assert logits.shape == (8, m.num_classes)
    else:
        assert logits.shape == (8, cfg.data.seq_len, m.vocab_size)
        widths = {d for leaf in jax.tree.leaves(variables["params"])
                  for d in leaf.shape}
        assert m.hidden_size in widths
