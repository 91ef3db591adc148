"""Sharding rules for the LM substrate (DESIGN.md §5).

One place defines how every logical tensor axis maps onto the production
mesh; model code only names logical axes. Layout:

  * batch        -> ("pod", "data")     activations, caches
  * vocab        -> "model"             embeddings / logits (fused CE)
  * heads / ffn  -> "model"             tensor parallelism
  * d_model      -> "data"              FSDP (ZeRO-3 style 2-D weight shard)
  * experts      -> "model"             expert parallelism (when divisible)
  * cache seq    -> "model" (+ "data" when batch == 1)   flash-decoding

`logical_to_spec` resolves a tuple of logical names to a PartitionSpec,
degrading gracefully when an axis is not divisible by the mesh extent
(falls back to replication for that axis — recorded so the dry-run report
can show which tensors degraded).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardRules:
    """Logical-axis -> mesh-axis mapping (None = replicate)."""

    batch: tuple[str, ...] = ("pod", "data")
    fsdp: Optional[str] = "data"      # d_model / reduction dims of weights
    tensor: Optional[str] = "model"   # heads / ffn / vocab / experts
    seq: Optional[str] = None          # sequence (context/sequence parallel)
    # sequence-parallel residual stream: activations at block boundaries are
    # sharded over this axis (Korthikanti et al. 2022). This is what keeps
    # the remat-saved (layers, B, L, D) stack inside HBM for the 70B-class
    # archs; GSPMD inserts the all-gather before / reduce-scatter after each
    # block — the LM analogue of the paper's halo-surface communication.
    seq_act: Optional[str] = "model"

    def for_mesh(self, mesh: Mesh) -> "ShardRules":
        names = set(mesh.axis_names)
        batch = tuple(a for a in self.batch if a in names)
        return ShardRules(
            batch=batch,
            fsdp=self.fsdp if self.fsdp in names else None,
            tensor=self.tensor if self.tensor in names else None,
            seq=self.seq if self.seq in names else None,
            seq_act=self.seq_act if self.seq_act in names else None,
        )


# paper-faithful baseline: pure data parallelism, replicated weights —
# the "naive translation" a ParallelStencil user would start from.
NAIVE_RULES = ShardRules(batch=("pod", "data"), fsdp=None, tensor=None,
                         seq=None, seq_act=None)
DEFAULT_RULES = ShardRules()


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def logical_to_spec(
    mesh: Mesh,
    rules: ShardRules,
    logical: Sequence[Optional[str]],
    dims: Sequence[int],
) -> P:
    """Resolve logical axis names to a PartitionSpec, checking divisibility.

    logical entries: "batch" | "fsdp" | "tensor" | "seq" | "seq+batch" |
    None (replicate).
    """
    rules = rules.for_mesh(mesh)
    # joint MoE resolution: when the expert dim divides the tensor axis the
    # experts shard over it (EP); otherwise the per-expert ffn dim picks up
    # the tensor axis (TP-inside-experts) and the capacity dim picks up the
    # batch axes so dispatch buffers never replicate (Mixtral: 8e vs 16-wide
    # tensor axis).
    expert_on_tensor = True
    if "expert" in logical and rules.tensor is not None:
        e_dim = dims[list(logical).index("expert")]
        expert_on_tensor = e_dim % _axis_size(mesh, rules.tensor) == 0
    out = []
    for name, dim in zip(logical, dims):
        target = None
        if name == "batch":
            target = rules.batch or None
        elif name == "fsdp":
            target = rules.fsdp
        elif name == "tensor":
            target = rules.tensor
        elif name == "expert":
            target = rules.tensor if expert_on_tensor else None
        elif name == "expert_ffn":
            target = None if expert_on_tensor else rules.tensor
        elif name == "moe_cap":
            target = None if expert_on_tensor else (rules.batch or None)
        elif name == "seq":
            target = rules.seq
        elif name == "seq_act":
            target = rules.seq_act
        elif name == "seq+batch":
            cand = tuple(a for a in ((rules.seq,) + rules.batch) if a)
            target = cand or None
        elif name in (None, "layers"):
            target = None  # "layers" is the scan-stacking axis — never sharded
        else:
            raise ValueError(f"unknown logical axis {name!r}")
        if target is not None:
            if isinstance(target, str):
                target = (target,)
            if dim % _axis_size(mesh, target) != 0:
                # degrade: drop trailing mesh axes until divisible
                while target and dim % _axis_size(mesh, target) != 0:
                    target = target[:-1]
                target = target or None
        if target is None:
            out.append(None)
        elif len(target) == 1:
            out.append(target[0])
        else:
            out.append(tuple(target))
    return P(*out)


def named(mesh: Mesh, rules: ShardRules, logical, dims) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, rules, logical, dims))


def constrain(x, mesh: Mesh, rules: ShardRules, logical):
    """with_sharding_constraint by logical names (no-op outside jit)."""
    spec = logical_to_spec(mesh, rules, logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# flash-decoding with a sequence-sharded KV cache
# ---------------------------------------------------------------------------
def seq_sharded_decode_attention(
    q, k_cache, v_cache, *, mesh: Mesh, seq_axes: tuple[str, ...],
    batch_axes: tuple[str, ...] = (), pos=None,
    window: Optional[int] = None, scale: Optional[float] = None,
):
    """Decode attention when the KV cache's sequence axis is sharded.

    Each shard computes a partial softmax (m, l, acc) over its local cache
    slice; partials combine with one pmax + two psums — O(B*H*D) bytes per
    device instead of all-gathering the cache (flash-decoding, adapted to
    the paper's "communicate only the reduced surface" discipline).

    q: (B, Hq, D) sharded over ``batch_axes``; caches (B, Hkv, S, D) with
    B over ``batch_axes`` and S over ``seq_axes``.
    """
    from ..compat import shard_map

    S = k_cache.shape[2]
    D = q.shape[-1]
    Hkv = k_cache.shape[1]
    scale_ = (D ** -0.5) if scale is None else scale
    bspec = _axes_entry(batch_axes)
    sspec = _axes_entry(seq_axes)

    def local_fn(q, kc, vc, pos_arr):
        b, Hq, _ = q.shape
        R = Hq // Hkv
        s_loc = kc.shape[2]
        # global offset of this shard's cache slice (row-major over seq_axes)
        off = jnp.int32(0)
        for ax in seq_axes:
            off = off * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        off = off * s_loc
        qg = q.reshape(b, Hkv, R, D).astype(jnp.float32) * scale_
        s = jnp.einsum("bgrd,bgkd->bgrk", qg, kc.astype(jnp.float32))
        kpos = off + jnp.arange(s_loc)
        mask = kpos <= pos_arr
        if window is not None:
            mask &= kpos > pos_arr - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bgrk,bgkd->bgrd", p, vc.astype(jnp.float32))
        # combine the partial softmaxes across the sequence shards
        m_g = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - m_g)
        l = jax.lax.psum(l * corr, seq_axes)
        acc = jax.lax.psum(acc * corr[..., None], seq_axes)
        l = jnp.where(l > 0, l, 1.0)
        return (acc / l[..., None]).reshape(b, Hq, D).astype(q.dtype)

    pos_arr = jnp.asarray(S - 1 if pos is None else pos, jnp.int32)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bspec), P(bspec, None, sspec, None),
                  P(bspec, None, sspec, None), P()),
        out_specs=P(bspec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, pos_arr)


def _axes_entry(axes: tuple[str, ...]):
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return tuple(axes)


def tuned_rules(cfg, mesh: Mesh) -> ShardRules:
    """Per-arch rule tuning from the §Perf hillclimb (EXPERIMENTS.md).

    * Pure-SSM archs whose head count does not divide the tensor axis
      (mamba2-130m: H=24 vs 16) waste the model axis — worse, GSPMD inserts
      per-layer gathers of the chunk-state tensors. The model axis joins
      data parallelism instead (measured: 11x collective reduction, §Perf m1).
    * seq_act=None everywhere: with 2-D-sharded weights the FSDP gathers
      are small, and sequence-parallel activations turned out to COST wire
      (gathers redone in remat + f32 boundary converts) — qwen2-72b train:
      tl 147s -> 65s and tc 24.3s -> 12.2s (§Perf q4, hypothesis q2 partially
      refuted). The activation-memory job moves to microbatching.
    """
    if getattr(cfg, "family", None) == "ssm":
        d_inner = cfg.ssm_expand * cfg.d_model
        heads = d_inner // max(cfg.ssm_head_dim, 1)
        tsize = mesh.shape.get("model", 1)
        if heads % tsize:
            return ShardRules(batch=("pod", "data", "model"), fsdp="data",
                              tensor=None, seq=None, seq_act=None)
    return ShardRules(seq_act=None)
