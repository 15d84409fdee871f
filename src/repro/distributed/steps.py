"""Distributed step builders: jit'd train / prefill / decode steps with
explicit in/out shardings for a (pod, data, model) mesh.

Each builder returns a :class:`StepBundle` — the jitted function plus
abstract, sharding-annotated arguments — so the multi-pod dry-run can
``bundle.fn.lower(*bundle.args).compile()`` without allocating anything,
and real launchers can feed concrete arrays with the same shardings.

MoE models default to the grouped GShard dispatch with one group per
data-parallel shard (``gshard:<G>``), the scalable formulation whose
dispatch/combine one-hots shard on (group, expert) — see models/moe.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import InputShape, ModelConfig
from ..models import model as model_lib
from ..models import pctx
from ..models import steps as steps_lib
from ..optim import adamw
from .sharding import (axis_size, batch_pspecs, cache_shardings, dp_axes,
                       param_shardings)

# Sharding-invariant RNG: without this, jax.random draws inside a jit with
# sharded out_shardings depend on the output partitioning, so the SAME
# PRNGKey yields DIFFERENT initial weights on different meshes (observed:
# body params diverging ~0.33 abs between a (2,2,2) mesh and single
# device, which then reads as a phantom distributed-numerics bug).
# Deliberately process-global (it is the upcoming JAX default): every
# random draw in this repo must use the partitionable stream, or states
# initialized through different entry points stop agreeing.
jax.config.update("jax_threefry_partitionable", True)


def auto_axes(mesh: Mesh) -> Mesh:
    """The step builders shard by placement (in/out shardings) and
    steer activations with sharding constraints, which is the Auto-axis
    model.  ``jax.make_mesh`` builds Explicit axes by default (JAX 0.7
    on), where those constraints and untyped gathers such as the
    embedding lookup raise; rebuild such a mesh with Auto axes."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# ---------------------------------------------------------------------------
# Abstract trees
# ---------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig, param_dtype=jnp.float32):
    return jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                      param_dtype))


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: model_lib.init_cache(cfg, batch, max_len))


def abstract_train_state(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                         param_dtype=jnp.float32):
    p = abstract_params(cfg, param_dtype)
    opt = jax.eval_shape(partial(adamw.init, cfg=opt_cfg), p)
    return {"params": p, "opt": opt}


def abstract_batch(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    out: Dict[str, Any] = {}
    i32 = jnp.int32
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B,), i32),
                "pos": jax.ShapeDtypeStruct((B,), i32)}
    if cfg.frontend == "audio":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.frontend_dim),
                                             jnp.float32)
    elif cfg.frontend == "vision":
        nf = cfg.n_frontend_tokens
        out["patch_embeds"] = jax.ShapeDtypeStruct((B, nf, cfg.frontend_dim),
                                                   jnp.float32)
        out["tokens"] = jax.ShapeDtypeStruct((B, S - nf), i32)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    if shape.kind == "train":
        tgt = (B, S - cfg.n_frontend_tokens) if cfg.frontend == "vision" \
            else (B, S)
        out["targets"] = jax.ShapeDtypeStruct(tgt, i32)
    return out


def _with_shardings(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def _batch_shardings(cfg, shape, mesh):
    specs = batch_pspecs(cfg, shape, mesh)
    return {k: NamedSharding(mesh, v) for k, v in specs.items()}


# ---------------------------------------------------------------------------
# MoE dispatch / hints
# ---------------------------------------------------------------------------


def _moe_groups(cfg: ModelConfig, shape: InputShape, mesh: Mesh) -> int:
    if cfg.moe is None:
        return 1
    dp = dp_axes(mesh)
    G = axis_size(mesh, dp)
    n_tok = shape.global_batch if shape.kind == "decode" \
        else shape.global_batch * shape.seq_len
    if G > 1 and n_tok % G == 0 and shape.global_batch % G == 0:
        return G
    return 1


def _moe_hints(mesh: Mesh, G: int):
    if G <= 1:
        return {}
    dp = dp_axes(mesh)
    dpx = dp if len(dp) > 1 else dp[0]
    return {
        "moe_dispatch": NamedSharding(mesh, P(dpx, None, "model", None)),
        "moe_expert_in": NamedSharding(mesh, P("model", dpx, None, None)),
        "moe_group_buf": NamedSharding(mesh, P(dpx, None, None, None)),
    }


def _dispatch_for(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                  override: Optional[str]) -> Tuple[Optional[str], dict]:
    if cfg.moe is None:
        return None, {}
    if override is not None:
        if override.startswith(("gshard", "sortg")):
            if ":" in override:
                G = int(override.split(":")[1])
            else:
                G = _moe_groups(cfg, shape, mesh)
                override = f"{override}:{G}"
            return override, _moe_hints(mesh, G)
        return override, {}
    G = _moe_groups(cfg, shape, mesh)
    return f"gshard:{G}", _moe_hints(mesh, G)


def _model_hints(cfg: ModelConfig, shape: InputShape, mesh: Mesh) -> dict:
    """Activation sharding constraints (models/pctx.py keys): keep the
    batch dim on the DP axes and put heads / FFN-hidden / vocab on "model"
    wherever the dimension divides — GSPMD propagation alone tends to lose
    batch sharding inside scanned attention and replicate (measured: full
    global-batch f32 all-reduces in the backward; see EXPERIMENTS.md)."""
    dp = dp_axes(mesh)
    nm = axis_size(mesh, ("model",))
    dpx = (dp if len(dp) > 1 else dp[0]) if (
        dp and shape.global_batch % axis_size(mesh, dp) == 0) else None
    heads = "model" if cfg.n_heads % nm == 0 else None
    kv = "model" if cfg.n_kv_heads % nm == 0 else None
    if cfg.mla is not None:
        kv = heads
    hints = {
        "activations": NamedSharding(mesh, P(dpx, None, None)),
        "attn_q": NamedSharding(mesh, P(dpx, None, heads, None)),
        "attn_kv": NamedSharding(mesh, P(dpx, None, kv, None)),
    }
    d_ff = cfg.moe.d_ff_dense or cfg.d_ff if cfg.moe else cfg.d_ff
    if d_ff and d_ff % nm == 0:
        hints["ffn_hidden"] = NamedSharding(mesh, P(dpx, None, "model"))
        hints["ffn_hidden_2d"] = NamedSharding(mesh, P(dpx, "model"))
    if cfg.vocab_size % nm == 0:
        hints["logits"] = NamedSharding(mesh, P(dpx, None, "model"))
    return hints


# ---------------------------------------------------------------------------
# Bundles
# ---------------------------------------------------------------------------


@dataclass
class StepBundle:
    name: str
    fn: Callable            # jitted step
    args: tuple             # abstract args (ShapeDtypeStruct trees)
    mesh: Mesh
    meta: dict = field(default_factory=dict)

    def lower(self):
        return self.fn.lower(*self.args)


# -- train ------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, mesh: Mesh, shape: InputShape,
                    opt_cfg: Optional[adamw.AdamWConfig] = None,
                    remat: bool = True, microbatch: int = 1,
                    dispatch: Optional[str] = None,
                    param_dtype=jnp.float32,
                    cast_params: bool = False,
                    extra_hints: Optional[dict] = None) -> StepBundle:
    """``cast_params=True`` casts fp32 master weights to the compute dtype
    ONCE at step entry, so FSDP all-gathers move bf16 instead of f32
    (half the wire + HBM traffic for every weight gather; the model's
    per-use ``astype`` then no-ops)."""
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    mesh = auto_axes(mesh)
    disp, hints = _dispatch_for(cfg, shape, mesh, dispatch)
    hints = {**_model_hints(cfg, shape, mesh), **hints,
             **(extra_hints or {})}

    state_abs = abstract_train_state(cfg, opt_cfg, param_dtype)
    state_sh = param_shardings(state_abs, mesh)
    batch_abs = abstract_batch(cfg, shape)
    batch_sh = _batch_shardings(cfg, shape, mesh)
    rep = NamedSharding(mesh, P())
    cdt = jnp.dtype(cfg.dtype)

    def loss_of(params, b):
        if cast_params:
            params = jax.tree.map(
                lambda p: p.astype(cdt)
                if p.dtype == jnp.float32 and p.ndim > 1 else p, params)
        return steps_lib.loss_fn(cfg, params, b, remat=remat, dispatch=disp)

    def step(state, batch):
        with pctx.sharding_hints(hints):
            params = state["params"]
            if microbatch > 1:
                def split(x):
                    return x.reshape((microbatch,
                                      x.shape[0] // microbatch) + x.shape[1:])
                mb = jax.tree.map(split, batch)

                def body(carry, b):
                    g_acc, loss_acc = carry
                    (loss, mets), g = jax.value_and_grad(
                        loss_of, has_aux=True)(params, b)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, loss_acc + loss), mets

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss), mets = jax.lax.scan(
                    body, (g0, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / microbatch, grads)
                loss = loss / microbatch
                metrics = jax.tree.map(lambda m: m[-1], mets)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, batch)
            new_params, new_opt, opt_metrics = adamw.update(
                params, grads, state["opt"], opt_cfg)
            metrics = {**metrics, **opt_metrics, "loss": loss}
            return {"params": new_params, "opt": new_opt}, metrics

    fn = jax.jit(step,
                 in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, rep),
                 donate_argnums=(0,))
    args = (_with_shardings(state_abs, state_sh),
            _with_shardings(batch_abs, batch_sh))
    return StepBundle("train", fn, args, mesh,
                      meta={"dispatch": disp, "remat": remat,
                            "microbatch": microbatch,
                            "state_shardings": state_sh,
                            "batch_shardings": batch_sh})


# -- prefill (encoder-only archs: full forward) -------------------------------


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: InputShape,
                      dispatch: Optional[str] = None,
                      param_dtype=jnp.float32,
                      extra_hints: Optional[dict] = None) -> StepBundle:
    mesh = auto_axes(mesh)
    disp, hints = _dispatch_for(cfg, shape, mesh, dispatch)
    hints = {**_model_hints(cfg, shape, mesh), **hints,
             **(extra_hints or {})}
    params_abs = abstract_params(cfg, param_dtype)
    params_sh = param_shardings(params_abs, mesh)
    batch_abs = abstract_batch(cfg, shape)
    batch_sh = _batch_shardings(cfg, shape, mesh)
    dp = dp_axes(mesh)
    dpx = (dp if len(dp) > 1 else dp[0]) if (
        dp and shape.global_batch % axis_size(mesh, dp) == 0) else None

    if cfg.encoder_only:
        def step(params, batch):
            with pctx.sharding_hints(hints):
                return model_lib.forward(cfg, params, batch, dispatch=disp)
        out_sh = NamedSharding(mesh, P(dpx, None, None))
        fn = jax.jit(step, in_shardings=(params_sh, batch_sh),
                     out_shardings=out_sh)
        args = (_with_shardings(params_abs, params_sh),
                _with_shardings(batch_abs, batch_sh))
        return StepBundle("encode", fn, args, mesh,
                          meta={"dispatch": disp,
                                "params_shardings": params_sh})

    cache_abs = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, shape.global_batch,
                                     shape.seq_len))
    cache_sh = cache_shardings(cache_abs, cfg, mesh, shape.global_batch)

    def step(params, batch):
        with pctx.sharding_hints(hints):
            return model_lib.prefill(cfg, params, batch, shape.seq_len,
                                     dispatch=disp)

    fn = jax.jit(step, in_shardings=(params_sh, batch_sh),
                 out_shardings=(NamedSharding(mesh, P(dpx, None)), cache_sh))
    args = (_with_shardings(params_abs, params_sh),
            _with_shardings(batch_abs, batch_sh))
    return StepBundle("prefill", fn, args, mesh,
                      meta={"dispatch": disp, "params_shardings": params_sh,
                            "cache_shardings": cache_sh})


# -- decode -------------------------------------------------------------------


def make_decode_step(cfg: ModelConfig, mesh: Mesh, shape: InputShape,
                     dispatch: Optional[str] = None,
                     param_dtype=jnp.float32,
                     cache_l_model: bool = False,
                     extra_hints: Optional[dict] = None) -> StepBundle:
    """One serve_step: each batch element appends one token against a KV /
    state cache of length seq_len.  ``cache_l_model`` shards the cache
    length dim over the "model" axis (flash-decoding)."""
    mesh = auto_axes(mesh)
    disp, hints = _dispatch_for(cfg, shape, mesh, dispatch)
    hints = {**_model_hints(cfg, shape, mesh), **hints,
             **(extra_hints or {})}
    B = shape.global_batch
    params_abs = abstract_params(cfg, param_dtype)
    params_sh = param_shardings(params_abs, mesh)
    cache_abs = abstract_cache(cfg, B, shape.seq_len)
    cache_sh = cache_shardings(cache_abs, cfg, mesh, B,
                               l_model=cache_l_model)
    dp = dp_axes(mesh)
    dpx = (dp if len(dp) > 1 else dp[0]) if (
        dp and B % axis_size(mesh, dp) == 0) else None
    tok_sh = NamedSharding(mesh, P(dpx))

    def step(params, cache, tokens, pos):
        with pctx.sharding_hints(hints):
            logits, new_cache = model_lib.decode_step(cfg, params, tokens,
                                                      pos, cache, disp)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, new_cache

    fn = jax.jit(step,
                 in_shardings=(params_sh, cache_sh, tok_sh, tok_sh),
                 out_shardings=(tok_sh, cache_sh),
                 donate_argnums=(1,))
    args = (_with_shardings(params_abs, params_sh),
            _with_shardings(cache_abs, cache_sh),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=tok_sh),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=tok_sh))
    return StepBundle("decode", fn, args, mesh,
                      meta={"dispatch": disp, "params_shardings": params_sh,
                            "cache_shardings": cache_sh})


def make_step_bundle(cfg: ModelConfig, mesh: Mesh, shape: InputShape,
                     **kw) -> StepBundle:
    """The step a given input shape exercises (assignment semantics)."""
    if shape.kind == "train":
        return make_train_step(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape, **kw)
    return make_decode_step(cfg, mesh, shape, **kw)


# re-exported alias
TrainState = dict


def input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                **kw) -> tuple:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    return make_step_bundle(cfg, mesh, shape, **kw).args
