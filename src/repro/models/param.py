"""Declarative parameter schema.

Each model declares a tree of ``ParamDef``s; from one declaration we derive
  * ``abstract(tree)``  -> ShapeDtypeStruct tree (dry-run: zero allocation)
  * ``specs(tree)``     -> PartitionSpec tree (in_shardings / checkpoints)
  * ``initialize(tree)``-> materialized arrays (deterministic per path)

Packed parameters: a ``ParamDef`` may declare named ``views`` splitting its
LAST axis (e.g. wqkv = [wq | wk | wv]) so several logical weights live in
one physical array and apply-time code issues ONE GEMM with zero copies.
``packing`` is the shard-interleave factor of the packed axis: with
``packing == g`` the columns are laid out shard-major — column block i of
the packed array holds [wq_i | wk_i | wv_i] (each view's i-th of g column
shards) — so a ``P(..., 'model')``-sharded packed array gives every model
shard contiguous per-view columns with no resharding.  ``split_views`` /
``pack_views`` convert between the packed layout and the logical per-view
arrays (checkpoints, reference math); they are exact mutual inverses.
Initialization draws each view with the seed stream of ``<path>/<view>``,
bitwise identical to declaring the views as separate ParamDefs.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: P = P()
    init: str = "normal"       # normal | zeros | ones | lru_log | custom
    scale: Optional[float] = None  # default: 1/sqrt(fan_in)
    dtype: str = "float32"
    custom: Optional[Callable[[jax.Array], jax.Array]] = None
    # packed param: named views splitting the last axis, e.g.
    # (("wq", q_dim), ("wk", kv_dim), ("wv", kv_dim)); sizes must sum to
    # shape[-1] and each must divide by ``packing`` (see module docstring)
    views: Optional[Tuple[Tuple[str, int], ...]] = None
    packing: int = 1

    def __post_init__(self):
        if self.views is not None:
            sizes = [s for _, s in self.views]
            assert sum(sizes) == self.shape[-1], (self.shape, self.views)
            assert all(s % self.packing == 0 for s in sizes), (
                self.views, self.packing)


def view_defs(d: ParamDef) -> Dict[str, ParamDef]:
    """Logical per-view ParamDefs of a packed def (same spec/init/dtype)."""
    assert d.views is not None
    return {name: ParamDef(d.shape[:-1] + (size,), d.spec, d.init, d.scale,
                           d.dtype, d.custom)
            for name, size in d.views}


def split_packed_columns(arr, sizes: Tuple[int, ...],
                         packing: int = 1) -> Tuple[Any, ...]:
    """Split the last axis of ``arr`` into per-view arrays.  Works on the
    packed weight AND on the output of a GEMM against it (activations
    inherit the packed column layout).  Plain basic indexing: traced jax
    arrays and host numpy arrays both stay what they are (checkpoint
    migration splits on host, no device round trip)."""
    lead = arr.shape[:-1]
    if packing == 1:
        off, out = 0, []
        for s in sizes:
            out.append(arr[..., off:off + s])
            off += s
        return tuple(out)
    a = arr.reshape(*lead, packing, sum(sizes) // packing)
    off, out = 0, []
    for s in sizes:
        sl = a[..., off:off + s // packing]
        out.append(sl.reshape(*lead, s))
        off += s // packing
    return tuple(out)


def split_views(d: ParamDef, arr: jax.Array) -> Dict[str, jax.Array]:
    """Packed array -> {view name: logical array}."""
    assert d.views is not None
    parts = split_packed_columns(arr, tuple(s for _, s in d.views),
                                 d.packing)
    return {name: p for (name, _), p in zip(d.views, parts)}


def pack_views(d: ParamDef, views: Dict[str, jax.Array]) -> jax.Array:
    """{view name: logical array} -> packed array (inverse of split_views).
    All-numpy inputs pack on host (checkpoint migration never bounces the
    unsharded array through a device)."""
    assert d.views is not None
    g = d.packing
    xp = np if all(isinstance(views[n], np.ndarray)
                   for n, _ in d.views) else jnp
    parts, lead = [], None
    for name, size in d.views:
        v = views[name]
        lead = v.shape[:-1]
        parts.append(v.reshape(*lead, g, size // g))
    packed = xp.concatenate(parts, axis=-1)
    return packed.reshape(*lead, packed.shape[-2] * packed.shape[-1])


def _path_seed(path: str, base: int) -> int:
    h = hashlib.md5(f"{base}:{path}".encode()).digest()
    return int.from_bytes(h[:4], "little")


def _init_one(d: ParamDef, path: str, base_seed: int,
              sharding: Optional[NamedSharding] = None) -> jax.Array:
    """Materialize one leaf.  With ``sharding`` the random draws are made
    in place at that sharding (the partitionable RNG gives the same
    values as an unsharded draw), so no device holds a whole large leaf
    on the way."""
    def place(arr):
        return arr if sharding is None else jax.device_put(arr, sharding)

    def draw(rng):
        if sharding is None:
            return rng(key)
        return jax.jit(rng, out_shardings=sharding)(key)

    if d.views is not None:
        # per-view streams at <parent>/<view> (the packed def's own name is
        # replaced by the view name): bitwise identical to declaring the
        # views as separate ParamDefs, so legacy checkpoints line up
        parent = path.rsplit("/", 1)[0]
        vs = {name: _init_one(vd, f"{parent}/{name}", base_seed)
              for name, vd in view_defs(d).items()}
        return place(pack_views(d, vs))
    key = jax.random.PRNGKey(_path_seed(path, base_seed))
    dt = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt, device=sharding)
    if d.init == "ones":
        return jnp.ones(d.shape, dt, device=sharding)
    if d.init == "lru_log":
        # RG-LRU Lambda init: uniform such that a = exp(-c*softplus(L)) has
        # moduli in [0.9, 0.999]
        u = draw(functools.partial(jax.random.uniform, shape=d.shape,
                                   dtype=jnp.float32, minval=0.9 ** 2,
                                   maxval=0.999 ** 2))
        lam = jnp.log(jnp.expm1(-0.5 * jnp.log(u) / 8.0))
        return lam.astype(dt)
    if d.init == "custom":
        # broadcast handles group-stacked defs (leading group axis added
        # after the custom fn was declared)
        return place(jnp.broadcast_to(d.custom(key), d.shape).astype(dt))
    # normal with fan-in scaling: fan_in = second-to-last dim by convention
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    scale = d.scale if d.scale is not None else 1.0 / np.sqrt(max(1, fan_in))
    return (draw(functools.partial(jax.random.normal, shape=d.shape,
                                   dtype=jnp.float32))
            * scale).astype(dt)


def _walk(tree: Any, fn: Callable[[ParamDef, str], Any], path: str = "") -> Any:
    if isinstance(tree, ParamDef):
        return fn(tree, path)
    if isinstance(tree, dict):
        return {k: _walk(v, fn, f"{path}/{k}") for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk(v, fn, f"{path}/{i}")
                          for i, v in enumerate(tree))
    raise TypeError(type(tree))


def abstract(tree: Any) -> Any:
    return _walk(tree, lambda d, p: jax.ShapeDtypeStruct(d.shape,
                                                         jnp.dtype(d.dtype)))


def specs(tree: Any) -> Any:
    return _walk(tree, lambda d, p: d.spec)


def initialize(tree: Any, seed: int = 0,
               mesh: Optional[Mesh] = None) -> Any:
    def mk(d: ParamDef, path: str):
        sharding = (NamedSharding(mesh, d.spec)
                    if mesh is not None and mesh.devices.size > 1 else None)
        return _init_one(d, path, seed, sharding)
    return _walk(tree, mk)


class _PassThrough:
    """Leaf marker in a legacy-``like`` tree for defs entries that are not
    ParamDefs (e.g. the optimizer step counter): restored as-is, shape
    unchecked."""


PASS_THROUGH = _PassThrough()


def unpack_defs(tree: Any) -> Any:
    """The legacy (unpacked) schema of the same model: every packed
    ParamDef is replaced by its per-view defs spliced into the PARENT dict
    as siblings (e.g. attn {"wqkv", "wo"} -> {"wq", "wk", "wv", "wo"}).
    Sibling splicing — not nesting under the packed name — is what makes
    the dict flatten order match checkpoints written before packing
    existed (jax flattens dicts in sorted-key order).  Non-ParamDef
    leaves pass through unchanged (mixed defs trees: optimizer state)."""
    if isinstance(tree, ParamDef):
        return view_defs(tree) if tree.views is not None else tree
    if isinstance(tree, dict):
        out: Dict[str, Any] = {}
        for k, v in tree.items():
            if isinstance(v, ParamDef) and v.views is not None:
                vd = view_defs(v)
                clash = (set(vd) & set(tree)) | (set(vd) & set(out))
                assert not clash, (k, clash)
                out.update(vd)
            else:
                out[k] = unpack_defs(v)
        return out
    if isinstance(tree, (list, tuple)):
        return type(tree)(unpack_defs(v) for v in tree)
    return tree


def unpack_like(defs: Any) -> Any:
    """Legacy-schema ``like`` tree of a (possibly mixed) defs tree:
    ParamDefs become ShapeDtypeStructs (packed ones splice their view
    structs into the parent as siblings), any other leaf becomes a
    PASS_THROUGH marker whose shape is not checked at restore."""
    def to_like(t: Any) -> Any:
        if isinstance(t, ParamDef):
            return jax.ShapeDtypeStruct(t.shape, jnp.dtype(t.dtype))
        if isinstance(t, dict):
            return {k: to_like(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            return type(t)(to_like(v) for v in t)
        return PASS_THROUGH
    return to_like(unpack_defs(defs))


def split_tree(defs: Any, values: Any) -> Any:
    """Packed value tree -> legacy value tree (checkpoint export), with
    views spliced into the parent dict exactly as ``unpack_defs`` lays
    the schema out."""
    if isinstance(defs, ParamDef):
        return (split_views(defs, values) if defs.views is not None
                else values)
    if isinstance(defs, dict):
        out: Dict[str, Any] = {}
        for k, v in defs.items():
            if isinstance(v, ParamDef) and v.views is not None:
                out.update(split_views(v, values[k]))
            else:
                out[k] = split_tree(v, values[k])
        return out
    if isinstance(defs, (list, tuple)):
        return type(defs)(split_tree(v, values[i])
                          for i, v in enumerate(defs))
    return values  # non-ParamDef leaf: pass through


def pack_tree(defs: Any, legacy_values: Any) -> Any:
    """Legacy value tree (per-view leaves as siblings, the pre-packing
    layout) -> packed value tree (checkpoint migration).  Non-ParamDef
    defs leaves pass their value through unchanged."""
    if isinstance(defs, ParamDef):
        return (pack_views(defs, legacy_values)
                if defs.views is not None else legacy_values)
    if isinstance(defs, dict):
        out: Dict[str, Any] = {}
        for k, v in defs.items():
            if isinstance(v, ParamDef) and v.views is not None:
                out[k] = pack_views(
                    v, {n: legacy_values[n] for n, _ in v.views})
            else:
                out[k] = pack_tree(v, legacy_values[k])
        return out
    if isinstance(defs, (list, tuple)):
        return type(defs)(pack_tree(v, legacy_values[i])
                          for i, v in enumerate(defs))
    return legacy_values  # non-ParamDef leaf: pass through


def n_params(tree: Any) -> int:
    total = [0]

    def count(d: ParamDef, path: str):
        n = 1
        for s in d.shape:
            n *= s
        total[0] += n
        return None

    _walk(tree, count)
    return total[0]
