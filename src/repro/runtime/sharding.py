"""Sharding rule engine: pytree path -> PartitionSpec.

Mesh axes (launch/mesh.py): single-pod ``("data", "model")`` = (16, 16);
multi-pod ``("pod", "data", "model")`` = (2, 16, 16).

Policy (DESIGN.md §5) — DP + FSDP + TP + EP:

* ``pod``   — pure data parallelism (params replicated across pods,
  gradient all-reduce crosses the pod axis only).
* ``data``  — batch sharding *and* FSDP: every large parameter also shards
  one non-TP dimension over 'data' (GSPMD all-gathers it around use).
* ``model`` — tensor parallelism: attention q-heads, MLP d_ff, Mamba
  d_inner channels, MoE experts (EP); GQA KV projections are small and
  stay replicated over 'model' so train-time attention needs no psum
  before the out-projection (Megatron f/g pattern).

Decode caches shard batch over 'data' and head_dim over 'model' (KV heads
are too few to shard; head_dim always divides); SSM states shard d_inner
over 'model'.  b=1 cells (long_500k) drop the batch axis and lean on
'model' alone — recorded per-cell in EXPERIMENTS.md.

Rules key on the LAST path component + rank, so the same table covers the
decoder-only stack (leaves carry a leading scan-group axis) and the
enc-dec stack.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# leaf name -> spec for the UNSTACKED rank (scan-group axis prepended
# automatically when the actual rank is one higher).
_RULES = {
    # attention
    "wq": ("data", "model", None),       # (D, H, hd)
    "wk": ("data", None, None),          # (D, KH, hd) — KV replicated over model
    "wv": ("data", None, None),
    "wo": ("model", None, "data"),       # (H, hd, D)
    # dense mlp
    "w_in": ("data", "model"),           # (D, F)
    "w_gate": ("data", "model"),
    "w_out": ("model", "data"),          # (F, D)
    # moe (rank 3 versions of w_in/w_gate/w_out handled below)
    "router": (None, None),              # (D, E) tiny — replicated
    # mamba
    "in_proj": ("data", "model"),        # (D, 2*di)
    "conv_w": (None, "model"),           # (k, di)
    "conv_b": ("model",),
    "x_proj": ("model", None),           # (di, R+2n)
    "dt_w": (None, "model"),             # (R, di)
    "dt_b": ("model",),
    "A_log": ("model", None),            # (di, n)
    "D": ("model",),
    "out_proj": ("model", "data"),       # (di, D)
    # embeddings
    "embed": ("model", "data"),          # (V, D)
    "lm_head": ("data", "model"),        # (D, V)
    "pos_embed": (None, "data"),         # (S, D)
    # norms
    "scale": (None,),
    "bias": (None,),
}

_MOE_RULES = {  # rank-3 expert-stacked weights: EP over 'model'
    "w_in": ("model", "data", None),     # (E, D, F)
    "w_gate": ("model", "data", None),
    "w_out": ("model", None, "data"),    # (E, F, D)
}


def abstract_mesh(axis_sizes: Tuple[int, ...],
                  axis_names: Tuple[str, ...]) -> "jax.sharding.AbstractMesh":
    """Device-free mesh for rule/divisibility checks, with Auto axes like
    every mesh :mod:`repro.launch.mesh` builds."""
    return jax.sharding.AbstractMesh(
        tuple(axis_sizes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names))


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            names.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            names.append(k.name)
        else:
            names.append(str(k))
    return tuple(names)


def param_pspec(path, ndim: int) -> P:
    names = _path_names(path)
    last = names[-1]
    rule = _RULES.get(last)
    if last in _MOE_RULES and ndim in (3, 4) and any("moe" in n for n in names):
        rule = _MOE_RULES[last]
    if rule is None:
        return P()
    if ndim == len(rule) + 1:  # stacked over scan groups / layers
        rule = (None,) + rule
    if ndim != len(rule):
        return P()  # unexpected rank: replicate rather than crash
    return P(*rule)


def tree_pspecs(tree) -> Any:
    """PartitionSpec pytree mirroring ``tree`` (of arrays or SDS)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, len(leaf.shape)), tree
    )


def filter_pspec(spec: P, mesh: Mesh, shape) -> P:
    """Drop mesh axes a dim can't divide evenly, and axes absent from mesh.

    GSPMD tolerates uneven sharding via padding, but padded shards waste
    memory and collectives; we only keep exact divisors (e.g. minicpm's 36
    heads on a 16-wide 'model' axis fall back to replicated — recorded as
    a known inefficiency, see DESIGN.md §6).
    """
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        keep = []
        size = 1
        for a in axes:
            if a in mesh.shape:
                keep.append(a)
                size *= mesh.shape[a]
        if keep and dim % size == 0:
            out.append(tuple(keep) if len(keep) > 1 else keep[0])
        else:
            out.append(None)
    return P(*out)


def tree_shardings(mesh: Mesh, tree,
                   fsdp_axes: Tuple[str, ...] = ("data",)) -> Any:
    """NamedSharding pytree for params/opt-state (rule-driven, mesh-aware).

    ``fsdp_axes=("pod", "data")`` is ZeRO-3 across pods: parameters and
    optimizer state shard over the pod axis too (cross-pod all-gather per
    layer) — required for models whose state exceeds one pod (jamba-398B,
    qwen3-235B; see EXPERIMENTS.md §Dry-run).
    """
    def one(path, leaf):
        spec = param_pspec(path, len(leaf.shape))
        if fsdp_axes != ("data",):
            spec = P(*(fsdp_axes if ax == "data" else ax for ax in spec))
        return NamedSharding(mesh, filter_pspec(spec, mesh, leaf.shape))

    return jax.tree_util.tree_map_with_path(one, tree)


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Data-parallel axes usable for this batch (largest prefix that divides)."""
    cand = [a for a in ("pod", "data") if a in mesh.shape]
    while cand:
        size = 1
        for a in cand:
            size *= mesh.shape[a]
        if global_batch % size == 0:
            return tuple(cand)
        cand.pop()  # drop 'data' last
    return ()


def batch_shardings(mesh: Mesh, cfg, batch_specs, global_batch: int) -> Any:
    dp = dp_axes(mesh, global_batch)
    dspec = dp if dp else None

    def one(path, leaf):
        names = _path_names(path)
        if names[-1] == "pos_ids":  # (3, b, s)
            return NamedSharding(mesh, P(None, dspec, None))
        spec = P(dspec, *([None] * (len(leaf.shape) - 1)))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, batch_specs)


def cache_shardings(mesh: Mesh, cfg, cache_specs, global_batch: int) -> Any:
    """KV caches: (G, b, S, KH, hd) -> batch over dp, hd over 'model'.
    SSM states: conv (G, b, k-1, di), ssm (G, b, di, n) -> di over 'model'."""
    dp = dp_axes(mesh, global_batch)
    dspec = dp if dp else None

    def one(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        last = names[-1]
        if last in ("k", "v", "ck", "cv"):
            spec = P(None, dspec, None, None, "model")
            if len(shape) == 4:  # encdec caches have no group axis... keep general
                spec = P(dspec, None, None, "model")
        elif last == "conv":
            spec = P(None, dspec, None, "model")
        elif last == "ssm":
            spec = P(None, dspec, "model", None)
        else:
            spec = P(*([None] * len(shape)))
        return NamedSharding(mesh, filter_pspec(spec, mesh, shape))

    return jax.tree_util.tree_map_with_path(one, cache_specs)


def pool_shardings(mesh: Mesh, cfg, cache_specs, n_slots: int) -> Any:
    """Serving slot-pool placement = the documented decode-cache policy.

    Slots (the pool's batch axis) shard over 'data', KV head_dim and SSM
    ``d_inner`` over 'model'; everything else replicates.  A pool narrower
    than the 'data' axis falls back to replicated rows (filter_pspec), so
    a TP-only serving mesh (1, M) is always legal.  Same rule table as
    training decode — the whole point of wiring serving onto the mesh is
    that there is exactly one placement policy for a decode cache.

    The PAGED pool's arenas reuse the same rule unchanged: a paged k/v
    leaf is (lead, n_pages, page_size, KH, hd) — still rank 5, with the
    page axis sitting where the slot axis sat — so the rank-5 k/v rule
    ``P(None, dspec, None, None, 'model')`` shards pages over 'data' and
    head_dim over 'model' with no paged-specific case here.  Non-paged
    leaves (ck/cv cross-KV, conv, ssm) keep slot-resident shapes and hit
    their usual rows.
    """
    return cache_shardings(mesh, cfg, cache_specs, n_slots)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# in-graph activation constraints
# ---------------------------------------------------------------------------
#
# GSPMD propagation alone does not reliably carry the 'model' sharding of
# attention heads into nested (remat(scan(map(scan)))) loop bodies at the
# production mesh: measured 16x device FLOPs on the first tinyllama
# dry-run (EXPERIMENTS.md §Perf, iteration 0).  The fix — standard in
# MaxText-class frameworks — is explicit with_sharding_constraint on
# activations inside the layers.  Layers call ``constrain(x, ...)`` with a
# template of {None, "model", "dp"}; the active mesh + data axes are
# provided by the step function through a contextvar at trace time, so the
# same layer code runs unconstrained in single-device tests.

import contextlib
import contextvars

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_act_ctx", default=None
)


@contextlib.contextmanager
def activation_context(mesh: Optional[Mesh], dp: Tuple[str, ...]):
    if mesh is None:
        yield
        return
    token = _ACT_CTX.set({"mesh": mesh, "dp": dp})
    try:
        yield
    finally:
        _ACT_CTX.reset(token)


def constrain(x, *template):
    """Apply a sharding constraint if an activation context is active.

    template entries per dim: None | mesh axis name | "dp" (the batch axes).
    Dims that don't divide their axes fall back to replicated (filter_pspec).
    """
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, dp = ctx["mesh"], ctx["dp"]
    axes = tuple((dp if dp else None) if a == "dp" else a for a in template)
    spec = filter_pspec(P(*axes), mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
