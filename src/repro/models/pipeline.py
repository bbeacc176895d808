"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

Completes the parallelism matrix (DP/TP/EP/SP elsewhere; PP here).  The
layer stack is split into `n_stages` contiguous groups; each stage lives
on one slice of the `pp` mesh axis (the `pod` axis on the two-pod mesh).
Microbatches stream through stages with `jax.lax.ppermute` boundary
transfers in a fori loop — the standard GPipe schedule (fill, steady
state, drain) with bubble fraction (S-1)/(M+S-1).

Scope: forward-and-loss is staged (activations cross pods once per
microbatch); the backward pass is produced by jax.grad through the
ppermute (its transpose is the reverse permute), which yields the
symmetric backward schedule automatically.

Usage (demonstrated in tests/test_pipeline.py on 4 host devices):
    fwd = make_pipelined_forward(cfg, n_stages=2, n_micro=4,
                                 axis_name="pod")
    loss = fwd(params, batch)  # inside shard_map over the pp axis
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import blocks
from repro.models.config import ModelConfig
from repro.models.layers import cross_entropy


def split_stages(cfg: ModelConfig, params: dict, n_stages: int):
    """Slice the scanned unit stack into per-stage stacks."""
    from repro.models.lm import unit_layout

    _, n_units = unit_layout(cfg)
    assert n_units % n_stages == 0, (n_units, n_stages)
    per = n_units // n_stages

    def slice_stage(s):
        return jax.tree.map(
            lambda a: a[s * per : (s + 1) * per], params["units"])

    return [slice_stage(s) for s in range(n_stages)], per


def make_pipelined_loss(cfg: ModelConfig, mesh: Mesh, *, n_micro: int,
                        pp_axis: str = "pod"):
    """Build a pipelined loss fn over ``pp_axis`` of ``mesh``.

    The returned function takes (params, batch) with params REPLICATED
    (each stage uses only its slice — the memory win comes from the
    optimizer/grad sharding, orthogonal here) and batch sharded over
    microbatches; it returns the mean loss.  Decoder-only dense/moe
    families (uniform units) are supported.
    """
    from repro.models.lm import _embed, _head, unit_layout

    n_stages = mesh.shape[pp_axis]
    kind, n_units = unit_layout(cfg)
    assert kind in ("dense", "moe"), "PP demo covers uniform decoders"
    assert n_units % n_stages == 0
    per = n_units // n_stages

    def stage_apply(stage_params, x, positions):
        def unit(xc, up):
            h, _, _ = blocks.decoder_layer_fwd(
                up, cfg, xc, positions, moe_layer=(kind == "moe"),
                mode="train", window=cfg.sliding_window)
            return h, None

        x, _ = jax.lax.scan(unit, x, stage_params)
        return x

    def local_fn(params, tokens, labels):
        # tokens: (n_micro_local..., B_mb, S) — each pp rank sees the SAME
        # microbatch stream; rank s processes stage s.
        stage_id = jax.lax.axis_index(pp_axis)
        my_stage = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(
                a, stage_id * per, per, axis=0), params["units"])
        B_mb, S = tokens.shape[1], tokens.shape[2]
        positions = jnp.arange(S, dtype=jnp.int32)[None]
        d = cfg.d_model

        n_steps = n_micro + n_stages - 1
        buf = jnp.zeros((B_mb, S, d), cfg.cdtype)
        loss_acc = jnp.zeros((), jnp.float32)

        def step(i, carry):
            buf, loss_acc = carry
            mb_in = jnp.clip(i, 0, n_micro - 1)
            x0 = _embed(cfg, params, tokens[mb_in])
            # Stage 0 ingests microbatch i (when valid); others use buf.
            x = jnp.where(stage_id == 0, x0.astype(buf.dtype), buf)
            y = stage_apply(my_stage, x, positions)
            # Shift stage outputs forward one rank.
            perm = [(s, s + 1) for s in range(n_stages - 1)]
            shifted = jax.lax.ppermute(y, pp_axis, perm) \
                if n_stages > 1 else y
            # Last stage emits loss for microbatch (i - (S-1)).
            mb_out = i - (n_stages - 1)
            valid = (mb_out >= 0) & (stage_id == n_stages - 1)
            lbl = labels[jnp.clip(mb_out, 0, n_micro - 1)]
            logits = _head(cfg, params, y)
            mb_loss = cross_entropy(logits, lbl)
            loss_acc = loss_acc + jnp.where(valid, mb_loss, 0.0)
            return shifted, loss_acc

        buf, loss_acc = jax.lax.fori_loop(0, n_steps, step,
                                          (buf, loss_acc))
        # Broadcast the last stage's loss to every rank.
        total = jax.lax.psum(
            jnp.where(jax.lax.axis_index(pp_axis) == n_stages - 1,
                      loss_acc, 0.0), pp_axis)
        return total / n_micro

    def pipelined(params, batch):
        tokens = batch["tokens"]          # (n_micro, B_mb, S)
        labels = jnp.concatenate(
            [tokens[:, :, 1:], jnp.full_like(tokens[:, :, :1], -1)],
            axis=2)
        fn = shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(params, tokens, labels)

    return pipelined


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
