"""Loss functions and in-step metrics.

SURVEY.md §2 row 9: softmax cross-entropy (+ weight decay, handled in the
optimizer chain) and top-1/top-5 metrics for the image models; masked-LM
cross-entropy for the BERT workload. All functions are pure and jit-safe;
losses are means over the *global* batch so that data-parallel gradient
aggregation is exactly the reference's SyncReplicasOptimizer mean.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def classification_loss(
    logits: jax.Array, labels: jax.Array, *, label_smoothing: float = 0.0
) -> tuple[jax.Array, dict[str, jax.Array]]:
    num_classes = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if label_smoothing > 0:
        onehot = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), label_smoothing
        )
        losses = optax.softmax_cross_entropy(logits, onehot)
    else:
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    loss = losses.mean()
    top1 = (jnp.argmax(logits, axis=-1) == labels).mean()
    metrics = {"loss": loss, "top1": top1}
    if num_classes > 5:
        top5_preds = jax.lax.top_k(logits, 5)[1]
        metrics["top5"] = (top5_preds == labels[:, None]).any(axis=-1).mean()
    return loss, metrics


def classification_metrics_sums(
    logits: jax.Array, labels: jax.Array, weight: jax.Array
) -> dict[str, jax.Array]:
    """Per-batch weighted metric SUMS for exact full-set evaluation.

    The eval loop (train/loop.py) accumulates these across the single-pass
    padded eval stream and divides by ``weight_sum`` at the end, so the
    result is the exact mean over real examples — zero-weight padding rows
    contribute nothing (reference eval-loop contract, SURVEY.md §3.4).
    """
    num_classes = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    out = {
        "loss_sum": (losses * w).sum(),
        "top1_sum": (correct * w).sum(),
        "weight_sum": w.sum(),
    }
    if num_classes > 5:
        top5 = (jax.lax.top_k(logits, 5)[1] == labels[:, None]).any(axis=-1)
        out["top5_sum"] = (top5.astype(jnp.float32) * w).sum()
    return out


def mlm_metrics_sums(
    logits: jax.Array, targets: jax.Array, weight: jax.Array
) -> dict[str, jax.Array]:
    """MLM weighted metric SUMS over masked positions (see above).

    ``weight_sum`` counts masked tokens of real (weight-1) examples — the
    exact denominator for masked-LM loss/accuracy.
    """
    mask = mlm_mask(targets) * weight.astype(jnp.float32)[:, None]
    loss_sum, correct_sum = mlm_sums(logits, targets, mask)
    return {
        "loss_sum": loss_sum,
        "mlm_acc_sum": correct_sum,
        "weight_sum": mask.sum(),
    }


def mlm_mask(targets: jax.Array) -> jax.Array:
    """1.0 at masked (predicted) positions, 0.0 elsewhere — the single
    definition of the '-1 means unmasked' sentinel, shared with the
    grad-accumulation microbatch weighting (train/step.py)."""
    return (targets >= 0).astype(jnp.float32)


def causal_lm_loss(
    logits: jax.Array, targets: jax.Array
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Next-token CE. ``targets[t]`` holds token ``t+1`` of the same
    document and -1 where there is none to predict (a document's last
    token, padding): the mean is over the labelled positions, the same
    sentinel and arithmetic as the masked-LM loss."""
    with jax.named_scope("loss"):
        loss, metrics = mlm_loss(logits, targets)
    return loss, {"loss": loss, "lm_acc": metrics["mlm_acc"]}


def mlm_sums(
    logits: jax.Array, targets: jax.Array, mask: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Masked-LM CE and top-1 hits, each SUMMED over the positions
    ``mask`` weights (default: the labelled ones). Takes any leading
    shape, so it serves all the positions of a batch and a window of them
    alike (models/bert.head_in_windows); the callers own the denominator."""
    logits = logits.astype(jnp.float32)
    if mask is None:
        mask = mlm_mask(targets)
    safe_targets = jnp.maximum(targets, 0)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, safe_targets)
    correct = (jnp.argmax(logits, axis=-1) == safe_targets).astype(jnp.float32)
    return (losses * mask).sum(), (correct * mask).sum()


def mlm_loss_of_sums(
    loss_sum: jax.Array, correct_sum: jax.Array, targets: jax.Array
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """The batch's masked-LM mean from :func:`mlm_sums` of its labelled
    positions, however they were gathered: the denominator is the whole
    batch's labelled count."""
    denom = jnp.maximum(mlm_mask(targets).sum(), 1.0)
    loss = loss_sum / denom
    return loss, {"loss": loss, "mlm_acc": correct_sum / denom}


def mlm_loss(
    logits: jax.Array, targets: jax.Array
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Masked-LM CE over whole rows of logits. ``targets`` holds the
    original token at masked positions and -1 elsewhere. The sums and
    the mean of :func:`mlm_sums` and :func:`mlm_loss_of_sums`, kept
    operation for operation as it was: a step that puts whole rows
    through it (``causal_lm``, a model without a windowed head) compiles
    to the program it had."""
    logits = logits.astype(jnp.float32)
    mask = mlm_mask(targets)
    safe_targets = jnp.maximum(targets, 0)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, safe_targets)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (losses * mask).sum() / denom
    correct = (jnp.argmax(logits, axis=-1) == safe_targets).astype(jnp.float32)
    acc = (correct * mask).sum() / denom
    return loss, {"loss": loss, "mlm_acc": acc}
