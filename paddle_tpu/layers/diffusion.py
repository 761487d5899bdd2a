"""The training step of block diffusion (BD3-LM, arXiv:2503.09573), as
SDAR trains with it (arXiv:2510.06303; docs/sdar.md).

A row of L clean ids x0 is cut into blocks of ``block`` tokens; block k has
a noise level t_k in (0, 1], and token i is replaced by ``mask_id`` where
v_i < t_blk(i), giving xt. The model runs once over the 2L positions
[xt ; x0] (layers/attention.py ``gqa_attention`` holds the mask that keeps a
clean position from seeing a noised one) and the loss is

    sum over masked i of (1 / t_blk(i)) * CE(logits_i, x0_i)

on the noised half: no shift, a masked position predicts its own token.
``block_diffusion_noise`` makes the 2L ids, ``block_diffusion_weights`` the
weights of the L noised positions (1 / t where masked, 0 elsewhere; the clean
half carries no loss and has none), ``noised_half`` cuts a 2L-position
sequence back to its first L.
Both read v [B, L] and t [B, ceil(L / block)] from feeds, so that program
and reference see one mask; a padding position is never masked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.core.layer import register_layer, register_step_stats
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.utils.error import enforce

_M_MASKED = obs_metrics.counter(
    "paddle_diffusion_masked_tokens_total",
    "Tokens a block_diffusion_noise layer replaced by the mask id: the "
    "positions that carry loss", labels=("layer",))
_M_POSITIONS = obs_metrics.counter(
    "paddle_diffusion_positions_total",
    "Positions a block_diffusion_noise layer handed to the model (noised "
    "and clean halves of the real tokens): what the step computes for the "
    "clean tokens it is given", labels=("layer",))


@register_step_stats("block_diffusion_noise")
def _publish_stats(lname, vec):
    masked, positions = (float(v) for v in vec)
    _M_MASKED.labels(layer=lname).inc(masked)
    _M_POSITIONS.labels(layer=lname).inc(positions)


def masked_and_level(ids: Arg, v, t, block):
    """(masked [B, L] bool, t of each token [B, L] float32)."""
    L = ids.value.shape[1]
    enforce(v.shape[1] == L and t.shape[1] == -(-L // block),
            f"block diffusion over rows of {L} tokens (as fed: the feeder "
            f"pads a batch's rows to one bucketed length) in blocks of "
            f"{block} needs v [B, {L}] and t [B, {-(-L // block)}]; got "
            f"{v.shape} and {t.shape}")
    level = jnp.repeat(t.astype(jnp.float32), block, axis=1)[:, :L]
    masked = (v.astype(jnp.float32) < level) & (ids.mask > 0)
    return masked, level


def _noise_infer(cfg, in_infos):
    return ArgInfo(size=in_infos[0].size, is_seq=True, dtype=jnp.int32)


@register_layer("block_diffusion_noise", infer=_noise_infer)
def _noise_forward(cfg, params, ins, ctx):
    """Inputs: ids (a sequence), v, t. Output: the ids [xt ; x0], [B, 2L]."""
    enforce(not getattr(ctx, "packed", False),
            f"block_diffusion_noise {cfg.name}: packed rows need the blocks "
            "cut per document, which this layer does not do")
    ids = ins[0]
    masked, _ = masked_and_level(ids, ins[1].value, ins[2].value,
                                 cfg.attr("block"))
    x0 = ids.value.astype(jnp.int32)
    xt = jnp.where(masked, jnp.int32(cfg.attr("mask_id")), x0)
    f32 = jnp.float32
    stats = jnp.stack([jnp.sum(masked.astype(f32)),
                       2.0 * jnp.sum(ids.mask.astype(f32))])
    ctx.extras.setdefault("step_stats", {}).setdefault(
        "block_diffusion_noise", {})[cfg.name] = stats
    return Arg(jnp.concatenate([xt, x0], axis=1),
               jnp.concatenate([ids.mask, ids.mask], axis=1))


def _weights_infer(cfg, in_infos):
    return ArgInfo(size=1, is_seq=True)


@register_layer("block_diffusion_weights", infer=_weights_infer)
def _weights_forward(cfg, params, ins, ctx):
    """Inputs as the noise layer's. Output: the loss weight of each of the
    L noised positions, float32 [B, L]."""
    masked, level = masked_and_level(ins[0], ins[1].value, ins[2].value,
                                     cfg.attr("block"))
    return Arg(jnp.where(masked, 1.0 / level, 0.0), ins[0].mask)


@register_layer("noised_half")
def _noised_half_forward(cfg, params, ins, ctx):
    """The first half of a sequence of 2L positions."""
    a = ins[0]
    L = a.value.shape[1] // 2
    enforce(a.value.shape[1] == 2 * L,
            f"noised_half {cfg.name}: {a.value.shape[1]} positions are not "
            "two halves")
    return Arg(a.value[:, :L], a.mask[:, :L])
