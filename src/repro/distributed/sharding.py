"""Logical-axis sharding: rules, adaptive resolution, activation constraints.

The framework names tensor dimensions with *logical* axes ("batch", "heads",
"mlp", "experts", "embed", "vocab", ...) and resolves them to mesh axes
through a rule table, MaxText-style. Resolution is **adaptive**: a dimension
only shards if its size divides the product of the mapped mesh axis sizes;
otherwise it stays replicated (and the decision is recorded). This is what
lets one rule table serve all 10 assigned architectures (e.g. kv_heads=4 or
even 1 cannot shard over a 16-way model axis — it silently replicates,
which is also what production systems do for GQA with narrow KV).

Parallelism mapping (see DESIGN.md §5):
  batch   -> ("pod", "data")   DP across pods and data axis
  embed   -> "data"            FSDP/ZeRO-3 on the d_model dim of weights
  heads/mlp/vocab/experts -> "model"   TP / EP
  seq     -> None by default; "data" under context/sequence parallelism
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Logical = Union[str, None, Tuple[str, ...]]

# default logical -> mesh-axis rule table
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),                 # replicated unless sequence-parallel enabled
    "embed": ("data",),        # FSDP on weight d_model rows
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
    "conv": (),
    "state": (),
    "expert_mlp": ("model",),
    "head_dim": ("model",),    # TP fallback when kv_heads < model axis
    "seq_kv": (),              # KV-cache length; "data" under context-parallel decode
}


def sequence_parallel_rules() -> Dict[str, Tuple[str, ...]]:
    r = dict(DEFAULT_RULES)
    r["seq"] = ("data",)
    r["batch"] = ("pod",)
    return r


def inference_rules() -> Dict[str, Tuple[str, ...]]:
    """Serving-time rule table (§Perf iteration C1).

    Training needs FSDP (optimizer state dominates); serving has no
    optimizer state, so weights replicate across the data axis (kills the
    per-layer FSDP all-gathers that dominated decode) and the KV cache
    shards its SEQUENCE dim over the model axis (context-parallel decode:
    per-layer attention over the cache becomes 1/16 local work + a tiny
    partial-softmax reduction, instead of full-cache traffic + the
    involuntary resharding the head_dim layout caused).
    """
    r = dict(DEFAULT_RULES)
    r["embed"] = ()            # no FSDP: weights replicated over data
    r["seq_kv"] = ("model",)   # context-parallel KV cache
    r["kv_heads"] = ()         # model axis belongs to seq_kv in decode
    r["head_dim"] = ()
    return r


RULE_SETS = {
    "default": DEFAULT_RULES,
    "sequence_parallel": None,   # resolved lazily below
    "inference": None,
}


def get_rules(name: str) -> Dict[str, Tuple[str, ...]]:
    if name in (None, "default"):
        return dict(DEFAULT_RULES)
    if name == "sequence_parallel":
        return sequence_parallel_rules()
    if name == "inference":
        return inference_rules()
    raise KeyError(name)


@dataclasses.dataclass
class ActiveSharding:
    mesh: Mesh
    rules: Dict[str, Tuple[str, ...]]
    notes: List[str] = dataclasses.field(default_factory=list)


_tls = threading.local()


def _active() -> Optional[ActiveSharding]:
    return getattr(_tls, "active", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Activate a mesh + rule table for `constrain` and spec resolution."""
    prev = _active()
    _tls.active = ActiveSharding(mesh, dict(rules or DEFAULT_RULES))
    try:
        with mesh:
            yield _tls.active
    finally:
        _tls.active = prev


def _mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    size = 1
    for a in axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return size


def resolve_axis(logical: Logical, dim: int, mesh: Mesh,
                 rules: Dict[str, Tuple[str, ...]],
                 notes: Optional[List[str]] = None):
    """Resolve one logical dim name to mesh axes (or None), adaptively."""
    if logical is None:
        return None
    if isinstance(logical, tuple):
        axes: Tuple[str, ...] = logical
    else:
        axes = tuple(rules.get(logical, ()))
    # keep only axes present in this mesh
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    size = _mesh_axis_size(mesh, axes)
    if size <= 1:
        return None
    if dim % size != 0:
        # try prefixes (e.g. batch over pod only if pod*data doesn't divide)
        for k in range(len(axes) - 1, 0, -1):
            sz = _mesh_axis_size(mesh, axes[:k])
            if sz > 1 and dim % sz == 0:
                if notes is not None:
                    notes.append(f"dim {dim} ({logical}): partial shard over {axes[:k]}")
                return axes[:k] if len(axes[:k]) > 1 else axes[0]
        if notes is not None:
            notes.append(f"dim {dim} ({logical}): replicated (not divisible by {size})")
        return None
    return axes if len(axes) > 1 else axes[0]


def resolve_spec(logical_axes: Sequence[Logical], shape: Sequence[int],
                 mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None,
                 notes: Optional[List[str]] = None) -> P:
    rules = dict(rules or DEFAULT_RULES)
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set = set()
    out = []
    for name, dim in zip(logical_axes, shape):
        r = resolve_axis(name, dim, mesh, rules, notes)
        # a mesh axis may appear at most once in a spec
        if r is not None:
            raxes = r if isinstance(r, tuple) else (r,)
            if any(a in used for a in raxes):
                r = None
            else:
                used.update(raxes)
        out.append(r)
    return P(*out)


def constrain(x: jax.Array, logical_axes: Sequence[Logical]) -> jax.Array:
    """Annotate intermediate activation sharding. No-op outside use_sharding."""
    act = _active()
    if act is None:
        return x
    spec = resolve_spec(logical_axes, x.shape, act.mesh, act.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(act.mesh, spec))


# ---------------------------------------------------------------------------
# parameter spec resolution by path pattern
# ---------------------------------------------------------------------------

# Matched against '/'.join(path keys); first hit wins. Leading 'layers/' stack
# dims are handled by the scan-stack rule below. Logical axes are per-dim,
# right-aligned to the array rank (missing leading dims -> None).
PARAM_RULES: List[Tuple[str, Tuple[Logical, ...]]] = [
    (r"(^|/)tok$", ("vocab", "embed")),
    (r"(^|/)head$", ("embed", "vocab")),
    (r"(^|/)wq$", ("embed", "heads", None)),
    (r"(^|/)wk$", ("embed", "kv_heads", None)),
    (r"(^|/)wv$", ("embed", "kv_heads", None)),
    (r"(^|/)wo$", ("heads", None, "embed")),
    (r"(^|/)bq$", ("heads", None)),
    (r"(^|/)b[kv]$", ("kv_heads", None)),
    (r"(^|/)w_gate$", ("embed", "mlp")),
    (r"(^|/)w_up$", ("embed", "mlp")),
    (r"(^|/)w_down$", ("mlp", "embed")),
    (r"(^|/)router$", ("embed", "experts")),
    (r"(^|/)e_gate$", ("experts", "embed", "expert_mlp")),
    (r"(^|/)e_up$", ("experts", "embed", "expert_mlp")),
    (r"(^|/)e_down$", ("experts", "expert_mlp", "embed")),
    # ssm in_proj/conv stay replicated on the packed zxBCdt dim: its split
    # points (z|xBC|dt) are not tile-aligned, so sharding it would force
    # all-gathers at every slice; the heads dim downstream carries the TP.
    (r"(^|/)in_proj$", ("embed", None)),
    (r"(^|/)out_proj$", ("mlp", "embed")),
    (r"(^|/)conv_w$", (None, None)),
    (r"(^|/)(A_log|dt_bias|D)$", ("mlp",)),
    (r"(^|/)(wx|wy)$", ("embed", "mlp")),     # rglru branches
    (r"(^|/)w_out$", ("mlp", "embed")),
    (r"(^|/)(a_param|in_gate_w|rec_gate_w)$", (None, None)),
    (r"(^|/)(in_gate_b|rec_gate_b|conv_b)$", (None,)),
    (r"(^|/)proj$", (None, "embed")),         # modality projector
    (r"(^|/)scale$", (None,)),                # norms replicated
    (r"(^|/)pos$", (None, None)),
]


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def logical_axes_for(path: str, ndim: int, scanned: bool) -> Tuple[Logical, ...]:
    for pat, axes in PARAM_RULES:
        if re.search(pat, path):
            axes = tuple(axes)
            if scanned:
                axes = ("layers",) + axes
            if len(axes) < ndim:  # right-align, pad leading None
                axes = (None,) * (ndim - len(axes)) + axes
            return axes[-ndim:] if len(axes) > ndim else axes
    return (None,) * ndim


def param_specs(params_shape, mesh: Mesh,
                rules: Optional[Dict[str, Tuple[str, ...]]] = None,
                notes: Optional[List[str]] = None):
    """Map a pytree of ShapeDtypeStructs/arrays -> pytree of PartitionSpecs.

    Params under a 'layers' subtree are scan-stacked: dim 0 is the layer axis
    and is never sharded.
    """
    rules = dict(rules or DEFAULT_RULES)

    def one(path, leaf):
        ps = path_str(path)
        scanned = ps.startswith("layers/") or "/layers/" in ps
        axes = logical_axes_for(ps, len(leaf.shape), scanned)
        return resolve_spec(axes, leaf.shape, mesh, rules, notes)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def named_shardings(params_shape, mesh: Mesh, rules=None, notes=None):
    specs = param_specs(params_shape, mesh, rules, notes)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# input-batch / cache spec resolution (dry-run + trainer + server)
# ---------------------------------------------------------------------------

DATA_RULES: List[Tuple[str, Tuple[Logical, ...]]] = [
    (r"(^|/)(tokens|labels|mask)$", ("batch", None)),
    (r"(^|/)(frames|patches)$", ("batch", None, None)),
    (r"(^|/)pos$", ()),
    (r"(^|/)[kv]$", ("batch", "seq_kv", "kv_heads", "head_dim")),
    (r"(^|/)ssm$", ("batch", "heads", "head_dim", "state")),
    (r"(^|/)conv$", ("batch", None, "mlp")),
    (r"(^|/)lru$", ("batch", "mlp")),
]


def data_specs(tree, mesh: Mesh, rules=None, notes=None):
    """Pytree of ShapeDtypeStructs -> PartitionSpecs for batches and caches.

    Logical axes are right-aligned to rank, so the same rule covers both a
    per-layer cache leaf (b, s, kv, hd) and a scan-stacked one (L, b, s, kv,
    hd) — the extra leading dim resolves to None.
    """
    rules = dict(rules or DEFAULT_RULES)

    def one(path, leaf):
        ps = path_str(path)
        nd = len(leaf.shape)
        for pat, axes in DATA_RULES:
            if re.search(pat, ps):
                ax = tuple(axes)
                if len(ax) < nd:
                    ax = (None,) * (nd - len(ax)) + ax
                return resolve_spec(ax[-nd:] if len(ax) > nd else ax,
                                    leaf.shape, mesh, rules, notes)
        return P()

    return jax.tree_util.tree_map_with_path(one, tree)


def data_shardings(tree, mesh: Mesh, rules=None, notes=None):
    specs = data_specs(tree, mesh, rules, notes)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# data-parallel staging (mesh-aware DeviceStager / sharded ERM backends)
# ---------------------------------------------------------------------------

def data_parallel_width(mesh: Optional[Mesh]) -> int:
    """How many ways the logical "batch" axis shards on this mesh — the
    product of the mesh axes the default rule table maps it to.  1 for a
    1-device mesh, a mesh with no pod/data axes, or ``mesh=None``."""
    if mesh is None:
        return 1
    return _mesh_axis_size(mesh, DEFAULT_RULES["batch"])


def replicated_shardings(template, mesh: Mesh):
    """Pytree of fully-replicated :class:`NamedSharding`\\ s over ``template``.

    The ERM solver state rides every mesh replicated (see
    ``repro.core.experiment``), so this is the target-sharding pytree for
    :meth:`repro.checkpoint.checkpointer.Checkpointer.restore`'s elastic
    path: a checkpoint saved on an 8-device mesh lands directly on a
    4-device (or 1-device) mesh's devices at restore time instead of
    bouncing through the default device."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: rep, template)


def staging_shardings(mesh: Mesh, batch_axes: Sequence[Sequence[Logical]],
                      shapes: Sequence[Sequence[int]],
                      notes: Optional[List[str]] = None,
                      ) -> Tuple[NamedSharding, ...]:
    """Resolve one :class:`NamedSharding` per staged array.

    ``batch_axes[i]`` names the logical axes of array ``i`` (e.g.
    ``(None, "batch", None)`` for a ``(K, b, n)`` chunk of K staged
    mini-batches); resolution is the same adaptive machinery the model
    layers use, so a batch dim that does not divide the mesh silently
    replicates — callers that need a hard guarantee (the planner) check
    divisibility up front."""
    return tuple(
        NamedSharding(mesh, resolve_spec(ax, shp, mesh, notes=notes))
        for ax, shp in zip(batch_axes, shapes))


@functools.lru_cache(maxsize=None)
def _replicate(mesh: Mesh):
    """The compiled reshard to fully replicated on ``mesh``: an identity
    program whose outputs are replicated, which XLA lowers to all-gathers
    over the interconnect, so the bytes never leave the devices.  Cached
    per mesh: every put built on one mesh (a job's warm-up and its live
    stager, and every later job) shares one compile cache."""
    return jax.jit(lambda *xs: xs, out_shardings=NamedSharding(mesh, P()))


def make_staging_put(mesh: Mesh, batch_axes: Sequence[Sequence[Logical]],
                     gather: bool = False, stats=None, tracer=None):
    """Build a ``put`` callable for :class:`repro.data.pipeline.DeviceStager`
    that places each host array as a GLOBAL array sharded on its batch axis
    (``jax.make_array_from_process_local_data``), so every device receives
    only its ``1/data_parallel_width`` slice over the host->device link.

    With ``gather=True`` the staged shards are then resharded to fully
    replicated by one compiled program (:func:`_replicate`: all-gathers
    over the interconnect, device to device), still inside the staging
    thread so it overlaps compute.  This is the ``reduction='gather'``
    staging mode: per-device H2D traffic drops by the mesh width while the
    consuming jit sees replicated inputs — bit-identical arithmetic to the
    single-host engines.  An array that is already replicated (a batch dim
    that does not divide the mesh) passes through unchanged.  The gather
    time, up to the all-gathers' completion, is recorded separately on
    ``stats`` (an :class:`~repro.data.pipeline.AccessStats`) so the H2D
    column keeps measuring the host link only; an enabled ``tracer``
    counts the resharded arrays (``gather.reshards``) and their bytes
    (``gather.bytes``)."""
    from ..obs import GATHER, NULL_TRACER

    tracer = tracer if tracer is not None else NULL_TRACER

    def put(host):
        shardings = staging_shardings(
            mesh, batch_axes, [np.asarray(a).shape for a in host])
        dev = tuple(
            jax.make_array_from_process_local_data(s, np.asarray(a))
            for a, s in zip(host, shardings))
        dev = jax.block_until_ready(dev)
        if gather:
            # the tracer span IS the measurement booked into stats — the
            # gather lane and gather_s cannot drift (they used to be two
            # separate perf_counter pairs waiting to diverge)
            with tracer.timespan("reshard", GATHER) as sp:
                dev = jax.block_until_ready(_replicate(mesh)(*dev))
            if stats is not None:
                stats.record_gather(sp.dur)
            if tracer.enabled:
                tracer.metrics.counter("gather.reshards").inc(len(dev))
                tracer.metrics.counter("gather.bytes").inc(
                    sum(a.nbytes for a in dev))
        return dev

    return put


def bytes_per_device(params_shape, mesh: Mesh, rules=None) -> int:
    """Parameter bytes resident per device under the resolved sharding."""
    specs = param_specs(params_shape, mesh, rules)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    for leaf, spec in zip(jax.tree.leaves(params_shape),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        shard = 1
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                shard *= axis_sizes.get(a, 1)
        total += n * leaf.dtype.itemsize // max(shard, 1)
    return total
