"""Jittable train / serve steps with full sharding resolution.

This is the glue between the model zoo, the optimizer, and the mesh:
  * resolve every parameter's logical names -> NamedSharding;
  * optimizer state shadows parameter shardings;
  * batch / cache shardings per DESIGN.md §5 (batch over ("pod","data"),
    KV-cache sequence over "model" — plus "data" when batch == 1, i.e. the
    long_500k flash-decoding layout);
  * build (step_fn, in_shardings, out_shardings) ready for jax.jit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed import sharding as shd
from ..models.model import Model
from ..optim import adamw


# ---------------------------------------------------------------------------
# sharding resolution
# ---------------------------------------------------------------------------
def param_shardings(model: Model, mesh: Mesh, rules: shd.ShardRules):
    shapes, logical = model.abstract_params()
    is_tpl = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)

    def resolve(lg, sh):
        return NamedSharding(mesh, shd.logical_to_spec(mesh, rules, lg, sh.shape))

    specs = jax.tree.map(resolve, logical, shapes,
                         is_leaf=lambda x: is_tpl(x))
    return shapes, specs


def opt_shardings(opt_state_shapes, p_shard, mesh: Mesh):
    """m/v/master shadow the param shardings; count is replicated."""
    rep = NamedSharding(mesh, P())
    out = {}
    for k, v in opt_state_shapes.items():
        out[k] = rep if k == "count" else p_shard
    return out


def batch_shardings(batch_specs, mesh: Mesh, rules: shd.ShardRules):
    def one(s):
        if s.ndim == 0:
            return NamedSharding(mesh, P())
        logical = ["batch"] + [None] * (s.ndim - 1)
        return NamedSharding(mesh, shd.logical_to_spec(mesh, rules, logical, s.shape))
    return jax.tree.map(one, batch_specs)


def cache_shardings(cache_shapes, mesh: Mesh, rules: shd.ShardRules,
                    global_batch: int):
    """KV caches (Ln, B, Hkv, S, Dh): B->batch, S->model (+data if B==1).
    Mamba states (Ln, B, H, P, N): H->tensor. Conv (Ln, B, K-1, Cin): Cin->tensor.
    Cross-attn memory (Ln, B, Hkv, S_src, Dh): like KV but source stays
    unsharded in seq (short)."""
    rules = rules.for_mesh(mesh)
    seq_axes = [rules.tensor] if rules.tensor else []
    if global_batch == 1:
        seq_axes = [a for a in (rules.fsdp, rules.tensor) if a]

    def one(name, s):
        if name in ("k", "v"):      # (Ln, B, Hkv, S, Dh): shard S (flash-decoding)
            spec = list(shd.logical_to_spec(
                mesh, rules, [None, "batch", None, None, None], s.shape))
            joint, sel = 1, []
            for a in seq_axes:
                if s.shape[3] % (joint * _size(mesh, a)) == 0:
                    sel.append(a)
                    joint *= _size(mesh, a)
            if sel:
                spec[3] = tuple(sel) if len(sel) > 1 else sel[0]
            return NamedSharding(mesh, P(*spec))
        if name in ("mk", "mv"):    # cross-attn memory (Ln, B, Hkv, S_src, Dh)
            return NamedSharding(mesh, shd.logical_to_spec(
                mesh, rules, [None, "batch", None, None, None], s.shape))
        if name == "ssm":           # (Ln, B, H, P, N)
            return NamedSharding(mesh, shd.logical_to_spec(
                mesh, rules, [None, "batch", "tensor", None, None], s.shape))
        if name == "conv":          # (Ln, B, K-1, Cin)
            return NamedSharding(mesh, shd.logical_to_spec(
                mesh, rules, [None, "batch", None, "tensor"], s.shape))
        logical = [None, "batch"] + [None] * (s.ndim - 2)
        return NamedSharding(mesh, shd.logical_to_spec(mesh, rules, logical, s.shape))

    flat, treedef = jax.tree.flatten_with_path(cache_shapes)
    out = []
    for kp, v in flat:
        path = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        out.append(one(path[-1] if path else "", v))
    return treedef.unflatten(out)


def _size(mesh, axes):
    import numpy as np
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    return int(np.prod([mesh.shape[a] for a in axes]))


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepBundle:
    fn: Any
    in_shardings: Any
    out_shardings: Any
    donate_argnums: tuple = ()
    abstract_inputs: tuple = ()


def make_train_step(model: Model, mesh: Mesh, rules: shd.ShardRules,
                    opt_cfg: adamw.AdamWConfig, seq_len: int,
                    global_batch: int, n_micro: int = 1) -> StepBundle:
    p_shapes, p_shard = param_shardings(model, mesh, rules)
    opt_shapes = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), p_shapes)
    o_shard = opt_shardings(opt_shapes, p_shard, mesh)
    b_specs = model.input_specs(seq_len, global_batch, "train")
    b_shard = batch_shardings(b_specs, mesh, rules)

    def constrain(x, logical):
        return shd.constrain(x, mesh, rules, logical)

    def train_step(params, opt_state, batch):
        if n_micro > 1:
            # gradient accumulation: scan over microbatches; activation
            # footprint shrinks by n_micro at the cost of an f32 grad
            # accumulator (param-sized, already sharded like the params).
            mb = jax.tree.map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                    *x.shape[1:]), batch)
            grads, loss = adamw.accumulate_grads(
                lambda p, b: model.loss_fn(p, b, constrain), params, mb, n_micro)
        else:
            loss, grads = jax.value_and_grad(model.loss_fn)(params, batch,
                                                            constrain)
        new_p, new_o, metrics = adamw.apply(params, grads, opt_state, opt_cfg)
        return new_p, new_o, {"loss": loss, **metrics}

    return StepBundle(
        fn=train_step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard,
                       {"loss": NamedSharding(mesh, P()),
                        "lr": NamedSharding(mesh, P()),
                        "grad_norm": NamedSharding(mesh, P())}),
        donate_argnums=(0, 1),
        abstract_inputs=(p_shapes, opt_shapes, b_specs),
    )


def make_prefill_step(model: Model, mesh: Mesh, rules: shd.ShardRules,
                      seq_len: int, global_batch: int,
                      max_seq: Optional[int] = None) -> StepBundle:
    max_seq = max_seq or seq_len
    p_shapes, p_shard = param_shardings(model, mesh, rules)
    b_specs = model.input_specs(seq_len, global_batch, "prefill")
    b_shard = batch_shardings(b_specs, mesh, rules)
    cache_shapes = jax.eval_shape(lambda: model.init_cache(global_batch, max_seq))
    c_shard = cache_shardings(cache_shapes, mesh, rules, global_batch)

    def constrain(x, logical):
        return shd.constrain(x, mesh, rules, logical)

    def prefill_step(params, batch):
        return model.prefill(params, batch, max_seq, constrain)

    V = model.cfg.vocab
    logits_shard = NamedSharding(
        mesh, shd.logical_to_spec(mesh, rules, ["batch", "tensor"],
                                  (global_batch, V)))
    return StepBundle(
        fn=prefill_step,
        in_shardings=(p_shard, b_shard),
        out_shardings=(logits_shard, c_shard),
        abstract_inputs=(p_shapes, b_specs),
    )


def make_decode_step(model: Model, mesh: Mesh, rules: shd.ShardRules,
                     seq_len: int, global_batch: int) -> StepBundle:
    """One-token decode against a cache of length seq_len."""
    p_shapes, p_shard = param_shardings(model, mesh, rules)
    d = model.input_specs(seq_len, global_batch, "decode")
    tok_shard = batch_shardings(d["token"], mesh, rules)
    c_shard = cache_shardings(d["cache"], mesh, rules, global_batch)
    pos_shard = NamedSharding(mesh, P())

    def constrain(x, logical):
        return shd.constrain(x, mesh, rules, logical)

    def decode_step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos, constrain)

    V = model.cfg.vocab
    logits_shard = NamedSharding(
        mesh, shd.logical_to_spec(mesh, rules, ["batch", "tensor"],
                                  (global_batch, V)))
    return StepBundle(
        fn=decode_step,
        in_shardings=(p_shard, tok_shard, c_shard, pos_shard),
        out_shardings=(logits_shard, c_shard),
        donate_argnums=(2,),
        abstract_inputs=(p_shapes, d["token"], d["cache"], d["pos"]),
    )
