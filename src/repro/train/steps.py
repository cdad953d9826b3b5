"""Production train / serve steps (pjit tier).

`make_train_step` builds one jit-able function:
    state, metrics = train_step(state, batch)
with the paper's communication relaxations attached at the gradient-exchange
point of the *sharded* trainer (the production tier of the two-tier
compression story — the exact per-worker algorithms live in
repro.core.communicators, the algorithm tier):

  * grad_compression='rq8'/...  — server-side compression of the device-owned
    gradient shard (the multi-server-PS view of Eq. 3.2: each device is the
    parameter server of its FSDP partition, so quantizing its shard is
    exactly the PS's outgoing Q; README.md "Compression story" records why
    worker-side Q is not interceptable under pjit autodiff). Compression is
    obtained from the Codec registry and runs through the FUSED flat-buffer
    tier: the whole gradient tree is flattened onto a FlatLayout and
    quantized per size-capped bucket in one pass — one message, one kernel
    launch, one (n_buckets, 2) params reduction, instead of one per pytree
    leaf. Metrics report the measured wire bytes of that one fused message.
  * error_feedback=True — single-sided DoubleSqueeze (Eq. 3.10-3.11) on the
    same shard: the residual delta is a SINGLE flat fp32 buffer in the
    train state (state['ec_err'], shape (n_params,)).
  * The exact two-sided algorithms live in repro.core.parallel (algorithm
    tier) and are validated against the theorems there.

`make_serve_step` builds the single-token decode step used by the decode
input shapes (decode_32k / long_500k) and the serving example.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import compression
from repro.dist import sharding
from repro.models import transformer, transformer_scan
from repro.models.common import ModelConfig
from repro.optim.optimizers import (Optimizer, apply_updates,
                                    clip_by_global_norm)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    remat: bool = False
    use_flash: bool = False
    grad_clip: float = 1.0
    grad_compression: str = "none"    # compression registry key
    error_feedback: bool = False      # single-sided EC on the grad shard
    param_dtype: Any = jnp.float32
    scan_layers: bool = False         # stacked params + lax.scan over blocks
    remat_policy: str = "full"        # full | dots (save matmul outputs)


def _impl(scan_layers: bool):
    return transformer_scan if scan_layers else transformer


def init_train_state(cfg: ModelConfig, optimizer: Optimizer, key: jax.Array,
                     *, step_cfg: TrainStepConfig = TrainStepConfig()) -> dict:
    params = _impl(step_cfg.scan_layers).init(cfg, key,
                                              dtype=step_cfg.param_dtype)
    state = {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": key,
    }
    if step_cfg.error_feedback:
        # single flat fp32 residual buffer over the whole gradient tree
        # (the fused-tier analogue of a per-leaf error pytree)
        total = compression.FlatLayout.from_tree(params).total
        state["ec_err"] = jnp.zeros((total,), jnp.float32)
    return state


def abstract_train_state(cfg: ModelConfig, optimizer: Optimizer, *,
                         step_cfg: TrainStepConfig = TrainStepConfig()):
    """ShapeDtypeStruct train state (dry-run: nothing is allocated)."""
    return jax.eval_shape(
        lambda k: init_train_state(cfg, optimizer, k, step_cfg=step_cfg),
        jax.random.PRNGKey(0))


def make_loss_fn(cfg: ModelConfig,
                 step_cfg: TrainStepConfig = TrainStepConfig()):
    """The production loss closure, ``loss(params, batch) -> scalar``.

    Factored out of ``make_train_step`` so other drivers — notably the
    virtual-cluster replay (``repro.cluster.execute``), which applies
    gradients in trace order rather than through one jit'd step — run the
    exact same forward/remat/flash configuration as production training.
    """
    impl = _impl(step_cfg.scan_layers)

    def loss(params, batch):
        kw = {}
        if step_cfg.scan_layers:
            kw["remat_policy"] = step_cfg.remat_policy
        with jax.named_scope("train.forward"):
            return impl.loss_fn(params, cfg, batch,
                                use_flash=step_cfg.use_flash,
                                remat=step_cfg.remat, **kw)

    return loss


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    step_cfg: TrainStepConfig = TrainStepConfig()):
    q_codec = compression.codec(step_cfg.grad_compression)

    loss_fn = make_loss_fn(cfg, step_cfg)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        loss_val, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(state["params"])
        with jax.named_scope("train.clip"):
            if step_cfg.grad_clip > 0:
                grads, grad_norm = clip_by_global_norm(grads,
                                                       step_cfg.grad_clip)
            else:
                grad_norm = jnp.zeros(())

        new_state = dict(state)
        comm_bytes = 0.0
        if step_cfg.grad_compression != "none":
            with jax.named_scope("train.codec"):
                qkey = jax.random.fold_in(state["rng"], state["step"])
            # fused flat-buffer path: flatten once (single-buffer writes,
            # layout from the lru cache), quantize per bucket in one
            # pass, ship ONE message
            layout = compression.FlatLayout.from_tree(grads)
            with jax.named_scope("train.flatten"):
                gflat = layout.flatten(grads)
            if step_cfg.error_feedback:
                # v survives the qdq (residual needs it) -> no donation
                with jax.named_scope("train.error_feedback"):
                    v = gflat + state["ec_err"]
                with jax.named_scope("train.codec"):
                    qflat = sharding.on_every_device(q_codec.flat_qdq)(
                        v, qkey)
                with jax.named_scope("train.error_feedback"):
                    new_state["ec_err"] = v - qflat
            else:
                # gflat is dead after the qdq -> donate its storage
                with jax.named_scope("train.codec"):
                    qflat = sharding.on_every_device(
                        partial(q_codec.flat_qdq, donate=True))(gflat, qkey)
            with jax.named_scope("train.unflatten"):
                grads = layout.unflatten(qflat)
            # measured wire bytes of the one fused gradient message (a
            # trace-time constant: shapes are static under jit)
            comm_bytes = q_codec.tree_wire_bytes_flat(grads)

        with jax.named_scope("train.optimizer"):
            updates, new_opt = optimizer.update(grads, state["opt"],
                                                state["params"])
            new_state["params"] = apply_updates(state["params"], updates)
            new_state["opt"] = new_opt
            new_state["step"] = state["step"] + 1
            metrics = {"loss": loss_val, "grad_norm": grad_norm,
                       "step": state["step"],
                       "comm_bytes": jnp.asarray(comm_bytes, jnp.float32)}
        return new_state, metrics

    return train_step


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------


def make_serve_step(cfg: ModelConfig, *, scan_layers: bool = False):
    """decode: (params, decode_state, inputs) -> (next_token_logits, state)."""
    impl = _impl(scan_layers)

    def serve_step(params, decode_state, inputs):
        logits, new_state = impl.decode_step(params, cfg, inputs,
                                             decode_state)
        return logits[:, -1], new_state

    return serve_step


def make_bulk_prefill(cfg: ModelConfig, *, scan_layers: bool = False):
    """Bulk cache fill: (params, decode_state, tokens (B, S)) ->
    (last_logits (B, V), filled decode_state) in ONE fused call.

    This is the recorded §Perf optimization that replaces the serving
    tier's token-by-token Python prompt loop (one dispatch per prompt
    position) with a single ``lax.scan`` of ``decode_step`` over the
    prompt axis — one compiled program, one dispatch, per prompt LENGTH
    instead of per prompt TOKEN. Because the scan body IS the decode
    step, the filled cache and the per-position logits are bit-identical
    to the incremental path by construction, across every block family
    (attn ring-buffer KV, MLA, RWKV/RG-LRU recurrent state) — asserted
    in tests/test_serve.py.

    Token-frontend models only (the serving engine's domain); the
    embedding frontends go through ``make_prefill_step`` below.
    """
    impl = _impl(scan_layers)
    if cfg.frontend != "token":
        raise ValueError(
            f"bulk prefill needs a token frontend, got '{cfg.frontend}'")

    def bulk_prefill(params, decode_state, tokens):
        def body(state, tok):
            logits, state = impl.decode_step(params, cfg,
                                             {"tokens": tok[:, None]}, state)
            return state, logits[:, -1]

        state, logits = jax.lax.scan(body, decode_state,
                                     jnp.moveaxis(tokens, 1, 0))
        return logits[-1], state

    return bulk_prefill


def make_prefill_step(cfg: ModelConfig, *, use_flash: bool = False,
                      scan_layers: bool = False,
                      logits_positions: str = "all"):
    """prefill: full-sequence forward returning last-position logits.

    (Cache population for subsequent decode goes through
    ``make_bulk_prefill`` above; this full-sequence forward remains the
    logits-only path the dry-run input shapes lower.)
    """

    impl = _impl(scan_layers)

    def prefill_step(params, batch):
        kw = {}
        if scan_layers:
            kw["logits_positions"] = logits_positions
        logits, _ = impl.apply(params, cfg, batch, use_flash=use_flash,
                               remat=scan_layers, **kw)
        return logits[:, -1]

    return prefill_step
