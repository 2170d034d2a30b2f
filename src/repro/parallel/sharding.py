"""Mesh-axis sharding rules: DP / FSDP / TP / EP / SP on (pod, data, model).

Philosophy (MaxText-style, but path-based): parameters are plain pytrees;
their PartitionSpec is derived from the tree path + rank by a rules table,
so model code stays annotation-free. Activations get explicit
`constraint(...)` calls at layer boundaries (that is where SP lives).

Axis semantics:
  pod    -- data parallelism across pods (slow DCI links)
  data   -- data parallelism within a pod; FSDP weight sharding; SP for
            long-context decode KV caches
  model  -- tensor parallelism (heads / ffn / vocab) and expert parallelism
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _STATE.mesh = prev


def axis(name: str):
    """Return `name` if present in the current mesh, else None (spec no-op)."""
    m = current_mesh()
    if m is None or name not in m.axis_names:
        return None
    return name


def batch_axes():
    """Batch shards over ('pod','data') when both exist, else ('data',)."""
    m = current_mesh()
    if m is None:
        return None
    names = [n for n in ("pod", "data") if n in m.axis_names]
    return tuple(names) if names else None


def seq_axis(T: int):
    """'model' if the live mesh can evenly shard a length-T sequence dim,
    else None (decode steps with T=1, odd tails, or no mesh)."""
    m = current_mesh()
    if m is None or "model" not in m.axis_names:
        return None
    size = dict(zip(m.axis_names, m.devices.shape))["model"]
    return "model" if T % size == 0 and T >= size else None


def constraint(x, *spec):
    """with_sharding_constraint if a mesh is active; identity otherwise.

    spec entries: 'batch' -> ('pod','data'); 'data'/'model'/'pod' -> axis if
    present; None -> replicated dim.
    """
    m = current_mesh()
    if m is None:
        return x
    resolved = []
    for s in spec:
        if s == "batch":
            resolved.append(batch_axes())
        elif isinstance(s, str):
            resolved.append(axis(s))
        else:
            resolved.append(s)
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*resolved)))


# ---------------------------------------------------------------------------
# Parameter sharding rules: (path regex, rank) -> spec template.
# Templates use symbols resolved against the live mesh:
#   D = fsdp axis ('data'), M = tensor axis ('model'), R = replicated (None),
#   DP = ('data','pod') fsdp over pods too (giant models).
# First match wins; default replicates.
# ---------------------------------------------------------------------------

PARAM_RULES: list[tuple[str, int, tuple]] = [
    # embeddings: (vocab, d_model) -- vocab TP + FSDP on d_model
    (r"embed/tok", 2, ("M", "D")),
    (r"lm_head", 2, ("D", "M")),          # (d_model, vocab)
    (r"embed/pos", 2, ("R", "D")),
    # hashed embedding compressed table (n_buckets, d_model)
    (r"embed/hashed", 2, ("M", "D")),
    # attention (fused-2D storage: (d_model, H*dh))
    (r"(attn|cross)/(wq|wk|wv)/w", 2, ("D", "M")),
    (r"(attn|cross)/wo/w", 2, ("M", "D")),
    (r"(attn|cross)/(wq|wk|wv|wo)/b", 1, ("R",)),
    # dense mlp
    (r"mlp/w_(gate|up)", 2, ("D", "M")),
    (r"mlp/w_down", 2, ("M", "D")),
    # moe experts: (n_experts, d_in, d_out) -- EP over model, FSDP inside
    (r"moe/(w_gate|w_up)", 3, ("M", "D", "R")),
    (r"moe/w_down", 3, ("M", "R", "D")),
    (r"moe/router", 2, ("D", "R")),       # (d_model, n_experts)
    (r"moe/shared", 2, ("D", "M")),       # shared-expert mlp handled as mlp
    # mamba
    (r"mamba/in_proj", 2, ("D", "M")),    # (d_model, 2*d_inner)
    (r"mamba/conv", 2, ("M", "R")),       # (d_inner, k)
    (r"mamba/x_proj", 2, ("M", "R")),     # (d_inner, dt_rank + 2*d_state)
    (r"mamba/dt_proj", 2, ("R", "M")),    # (dt_rank, d_inner)
    (r"mamba/(A_log|D)$", 2, ("M", "R")),
    (r"mamba/(A_log|D)$", 1, ("M",)),
    (r"mamba/out_proj", 2, ("M", "D")),
    (r"mamba/dt_bias", 1, ("M",)),
    # rwkv6
    (r"rwkv/w_(r|k|v|g)", 2, ("D", "M")),
    (r"rwkv/w_o", 2, ("M", "D")),
    (r"rwkv/(decay|bonus|mix)", None, ("M",)),  # per-channel vectors
    (r"rwkv/ffn_(k)", 2, ("D", "M")),
    (r"rwkv/ffn_(v|r)", 2, ("M", "D")),
    # norms / scalars: replicated
    (r"(norm|scale|bias|ln)", None, ()),
]


# Serving-mode overrides: MoE expert weights stay 2D-sharded even for
# inference (E over model, F over data) -- a 400B-expert pool cannot be
# TP-16-resident (50 GiB/chip), but it IS resident at E/16 x F/16
# (~3.1 GiB/chip) and the dispatch all-to-all already routes tokens.
SERVING_OVERRIDES: list[tuple[str, int, tuple]] = [
    (r"moe/(w_gate|w_up)", 3, ("M", "R", "D!")),
    (r"moe/w_down", 3, ("M", "D!", "R")),
]


def _resolve(sym, fsdp_pods: bool, serving: bool = False):
    if sym == "D!":  # data axis regardless of serving mode
        return axis("data")
    if sym == "D":
        if serving:
            # TP-RESIDENT weights for inference: no FSDP dim, weights
            # replicated over 'data' and sharded over 'model' only --
            # decode must never all-gather weights (latency = HBM read of
            # the resident shard). See results/perf_log.md it4.
            return None
        names = [n for n in (("data", "pod") if fsdp_pods else ("data",)) if axis(n)]
        if not names:
            return None
        # bare axis for the single-name case: this jax's PartitionSpec no
        # longer equates P(('data',)) with P('data'), and every consumer
        # (NamedSharding, _axis_size) accepts the bare name
        return names[0] if len(names) == 1 else tuple(names)
    if sym == "M":
        return axis("model")
    return None


def _axis_size(mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= _axis_size(mesh, n)
        return out
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def spec_for(path: str, shape: tuple, fsdp_pods: bool = False,
             serving: bool = False) -> P:
    """PartitionSpec for a parameter at pytree `path` with given shape.

    Dims whose size is not divisible by the proposed mesh-axis extent are
    replicated instead (explicit jit in_shardings require divisibility --
    e.g. 8 kv heads cannot TP-shard over model=16, so they replicate;
    with 56 q-heads over model=16 we drop to replicated as well and the
    head einsums re-shard internally via activation constraints).
    """
    ndim = len(shape)
    # Layer stacks (scan-over-layers) live under layers/blocks keys by
    # convention: their leading dim is the scan dim, replicated.
    stacked = bool(re.search(r"(^|/)(layers|blocks|enc_layers|dec_layers)(/|$)", path))
    eff_ndim = ndim - 1 if stacked else ndim
    eff_shape = shape[1:] if stacked else shape
    mesh = current_mesh()
    rules = (SERVING_OVERRIDES + PARAM_RULES) if serving else PARAM_RULES
    for pat, rank, template in rules:
        if re.search(pat, path) and (rank is None or rank == eff_ndim):
            syms = list(template)[:eff_ndim]
            syms += ["R"] * (eff_ndim - len(syms))
            spec = [_resolve(s, fsdp_pods, serving) for s in syms]
            if mesh is not None:
                spec = [
                    s if (s is None or eff_shape[i] % _axis_size(mesh, s) == 0)
                    else None
                    for i, s in enumerate(spec)
                ]
            if stacked:
                spec = [None] + spec
            return P(*spec)
    return P(*([None] * ndim))


def tree_paths(tree):
    """Pytree -> list of (path_str, leaf). Path uses '/'-joined dict keys."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out


def param_specs(params, fsdp_pods: bool = False, serving: bool = False):
    """Tree of PartitionSpec mirroring `params`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
        path = "/".join(parts)
        specs.append(spec_for(path, tuple(getattr(leaf, "shape", ())),
                              fsdp_pods, serving))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params, mesh: Mesh, fsdp_pods: bool = False):
    specs = param_specs(params, fsdp_pods)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh, ndim: int):
    """Input batch: dim 0 over ('pod','data'), rest replicated."""
    names = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    return NamedSharding(mesh, P(names, *([None] * (ndim - 1))))


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """`jax.make_mesh` with Auto axes. The rules above place arrays with
    explicit shardings and let GSPMD propagate the rest; `jax.make_mesh`'s
    default Explicit axes would instead type every sharded array and reject
    slices and gathers whose output sharding is ambiguous."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def data_mesh(max_devices: int | None = None) -> Mesh:
    """1-D ('data',) mesh over the live device set -- the scale-out substrate
    for `repro.hash.distributed` (FUNCTION, not constant: importing never
    touches device state). On a single-device host this is a mesh of size 1
    and every shard_map over it runs the plain single-device code path."""
    n = len(jax.devices())
    if max_devices is not None:
        n = min(n, max_devices)
    return make_mesh((n,), ("data",))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    """Extent of `name` in `mesh` (1 if absent -- degenerate degrade)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get(name, 1))
