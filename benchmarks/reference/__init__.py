"""Plain references: forward, loss and ``jax.grad`` in ``jax.numpy`` and
float32, written from the papers, importing nothing from the package.

A family module has one function::

    loss_and_grad_norm(params, batch, hparams) -> (loss, grad_norm)

``params`` is the program's parameter tree (plain nested dicts of
arrays, walked by name); ``batch`` the sample's arrays; ``hparams`` the
few published sizes the walk needs. The caller sets
``jax.default_matmul_precision("highest")``: on a TPU a float32 matrix
multiplication otherwise runs in bfloat16 passes.
"""

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))
