"""Jaxpr (de)serialization: the module-transfer wire format.

Reference parity: TePDist ships the whole-graph HloModuleProto (plus
DefContext tree) from client to master and master to slaves
(``TransferModuleAndDefCtx``, reference: service/hlo.proto:543-582). The
TPU-native client's IR is the jaxpr, so the wire format is a serialized
*inlined* ClosedJaxpr: tagged JSON for structure + raw little-endian bytes
for array literals/consts. Call-like equations must be inlined before
serialization (function-valued params such as custom_jvp rules are not
serializable by design); control-flow sub-jaxprs (scan/while/cond) serialize
recursively.

The deserializer rebuilds real JaxprEqns against the live primitive registry,
so the server can plan (JaxprGraph) and execute (primitive.bind) the received
module exactly as a locally-traced one.
"""

from __future__ import annotations

import base64
import enum
import json
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
from jax.extend import core as jexcore
from jax._src import core as _core

import logging
log = logging.getLogger(__name__)

# Prims that ARE effects at the leaf level (Ref read/write inside pallas
# kernels; state primitives; host interaction). Call-like prims (scan/
# while/cond/pjit/shard_map/remat/custom_*) are handled STRUCTURALLY
# instead: their decoded sub-jaxpr
# params carry recomputed effects, so an eqn re-runs abstract_eval only
# when an inner effect actually exists — effect-free bodies (the RPC hot
# path) decode without paying a recursive abstract_eval.
_LEAF_EFFECT_PRIMS = frozenset({
    # state / pallas kernel-side primitives (the registry registers
    # jax._src.state.primitives and jax._src.pallas.primitives)
    "get", "swap", "addupdate", "masked_swap",
    "atomic_rmw", "atomic_cas", "run_scoped",
    "semaphore_signal", "semaphore_wait", "semaphore_read",
    "debug_print", "debug_callback",
    # host-interaction prims: ordered effects by construction
    "infeed", "outfeed", "io_callback", "pure_callback",
})


def _may_carry_effects(prim, params: dict) -> bool:
    """Leaf-effect whitelist, plus the structural check: any eqn whose
    decoded sub-jaxpr params carry effects must be re-abstract-eval'd so
    the effects propagate to this eqn."""
    if prim.name in _LEAF_EFFECT_PRIMS:
        return True
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, _core.Jaxpr) and x.effects:
                return True
            if isinstance(x, jexcore.ClosedJaxpr) and x.jaxpr.effects:
                return True
    return False


# --------------------------------------------------------------------------
# Primitive registry
# --------------------------------------------------------------------------

def _build_primitive_registry() -> Dict[str, Any]:
    registry: Dict[str, Any] = {}
    from jax.extend.core import primitives as _prims
    import jax._src.lax.lax as m1
    import jax._src.lax.control_flow as m2
    import jax._src.lax.slicing as m3
    import jax._src.lax.convolution as m4
    import jax._src.lax.windowed_reductions as m5
    import jax._src.lax.special as m6
    import jax._src.lax.linalg as m7
    import jax._src.lax.ann as m8
    import jax._src.prng as m9
    import jax._src.ad_util as m10
    import jax._src.lax.parallel as m11
    import jax._src.ad_checkpoint as m11b  # name_p / remat_p
    import jax._src.shard_map as m12       # shard_map_p: the SPMD wrapper
    import jax._src.pjit as m13            # sharding_constraint_p etc.
    # Pallas kernels ship over RPC as first-class jaxprs: the call
    # primitive itself, the in-kernel Ref state primitives (get/swap/
    # addupdate), and pallas helper prims (program_id etc.).
    import jax._src.pallas.pallas_call as m14
    import jax._src.pallas.primitives as m15
    import jax._src.state.primitives as m16
    # _core carries pvary_p (vma adjustment).
    modules = [_prims, m1, m2, m3, m4, m5, m6, m7, m8, m9, m10, m11, m11b,
               m12, m13, _core, m14, m15, m16]
    for mod in modules:
        for name in dir(mod):
            obj = getattr(mod, name, None)
            if isinstance(obj, _core.Primitive):
                registry.setdefault(obj.name, obj)
    return registry


_PRIMITIVES: Dict[str, Any] = _build_primitive_registry()


def primitive_by_name(name: str):
    p = _PRIMITIVES.get(name)
    if p is None:
        raise KeyError(
            f"primitive {name!r} not in registry ({len(_PRIMITIVES)} known); "
            "extend _build_primitive_registry")
    return p


# Named tuples / enums that appear in lax params.
import dataclasses as _dc

import jax._src.pallas.core as _pl_core
import jax._src.pallas.mosaic.core as _pl_tpu_core
from jax import lax as _lax
from jax._src.frozen_dict import FrozenDict as _FrozenDict

_NAMEDTUPLES = {
    "ConvDimensionNumbers": _lax.ConvDimensionNumbers,
    "GatherDimensionNumbers": _lax.GatherDimensionNumbers,
    "ScatterDimensionNumbers": _lax.ScatterDimensionNumbers,
}
_ENUMS = {
    "GatherScatterMode": _lax.GatherScatterMode,
    "Precision": _lax.Precision,
    "RandomAlgorithm": _lax.RandomAlgorithm,
    "PallasMemorySpace": _pl_core.MemorySpace,
    # A kernel's ``scratch_shapes=[pltpu.VMEM(...)]`` (the flash backward's
    # dQ^T accumulator): the scratch Ref's aval names the TPU's own enum.
    "PallasTpuMemorySpace": _pl_tpu_core.MemorySpace,
}


# --------------------------------------------------------------------------
# PyTreeDef encoding (pallas get/swap `tree` params, GridMapping trees).
#
# A PyTreeDef is encoded structurally via node_data()/children() and rebuilt
# on the receiver by constructing a template pytree (with opaque leaf
# markers) and taking its tree_structure. Custom nodes are limited to the
# allowlist below — the indexing types pallas state primitives put in their
# treedefs — so an unknown custom node fails loudly at serialization time
# rather than decoding wrongly.
# --------------------------------------------------------------------------

def _treedef_node_types() -> Dict[str, Any]:
    from jax._src.state.indexing import NDIndexer, Slice
    return {"tuple": tuple, "list": list, "dict": dict,
            "NoneType": type(None), "NDIndexer": NDIndexer, "Slice": Slice}


_TREEDEF_NODES = _treedef_node_types()


class _TreeLeaf:
    """Opaque leaf marker used when rebuilding treedef templates."""


def _enc_treedef(td) -> dict:
    nd = td.node_data()
    if nd is None:
        return {"k": "leaf"}
    cls, aux = nd
    name = cls.__name__
    if name not in _TREEDEF_NODES:
        raise TypeError(f"treedef custom node {name!r} not serializable; "
                        "extend _treedef_node_types")
    return {"k": "node", "cls": name, "aux": encode_value(aux),
            "children": [_enc_treedef(c) for c in td.children()]}


def _dec_treedef_template(d: dict) -> Any:
    if d["k"] == "leaf":
        return _TreeLeaf()
    cls = _TREEDEF_NODES[d["cls"]]
    children = [_dec_treedef_template(c) for c in d["children"]]
    aux = decode_value(d["aux"])
    if cls is tuple:
        return tuple(children)
    if cls is list:
        return list(children)
    if cls is dict:
        return dict(zip(aux, children))
    if cls is type(None):
        return None
    return cls.tree_unflatten(aux, children)


def _dec_treedef(d: dict):
    return jax.tree_util.tree_structure(_dec_treedef_template(d))


# --------------------------------------------------------------------------
# Value encoding
# --------------------------------------------------------------------------

def _is_key_array(x) -> bool:
    dt = getattr(x, "dtype", None)
    return dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.extended)


def _keyimpl_name(dtype) -> str:
    """Extended-dtype support is PRNG keys only; anything else is a clear
    error rather than a silent mis-encode."""
    if jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
        return dtype._impl.name
    raise TypeError(f"cannot serialize extended dtype {dtype!r} "
                    "(only PRNG key dtypes are supported)")


def _key_dtype(impl_name: str):
    from jax._src import prng as _prng
    return _prng.KeyTy(_prng.prngs[impl_name])


def _enc_array(x) -> dict:
    dt = getattr(x, "dtype", None)
    if dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.extended):
        # Typed PRNG keys (key<fry> etc.): the wire carries the raw uint32
        # key data plus the impl name; the receiver rebuilds the typed array
        # with jax.random.wrap_key_data. Reference analogue: opaque-typed
        # HLO constants round-trip by value+type, hlo.proto:543-582.
        name = _keyimpl_name(dt)
        data = np.asarray(jax.random.key_data(x))
        return {"t": "ndarray", "dtype": "key:" + name,
                "shape": list(x.shape),
                "data": base64.b64encode(
                    np.ascontiguousarray(data).tobytes()).decode(),
                "keydata_dtype": data.dtype.name,
                "keydata_shape": list(data.shape)}
    x = np.asarray(x)
    if x.dtype == jax.dtypes.float0:
        # float0 (symbolic-zero cotangents for integer primals) has
        # itemsize 0 — there are no bytes to ship, only the shape.
        return {"t": "ndarray", "dtype": "float0", "shape": list(x.shape),
                "data": ""}
    return {
        "t": "ndarray",
        "dtype": x.dtype.name,
        "shape": list(x.shape),
        "data": base64.b64encode(np.ascontiguousarray(x).tobytes()).decode(),
    }


def _dec_array(d: dict):
    if d["dtype"] == "float0":
        return np.zeros(d["shape"], dtype=jax.dtypes.float0)
    if d["dtype"].startswith("key:"):
        buf = base64.b64decode(d["data"])
        data = np.frombuffer(
            buf, dtype=np.dtype(d["keydata_dtype"])).reshape(
                d["keydata_shape"])
        return jax.random.wrap_key_data(
            jax.numpy.asarray(data), impl=d["dtype"][4:])
    buf = base64.b64decode(d["data"])
    return np.frombuffer(buf, dtype=np.dtype(d["dtype"])).reshape(d["shape"])


def encode_value(v: Any) -> Any:
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.dtype):
        return {"t": "dtype", "v": v.name}
    if isinstance(v, type) and issubclass(v, np.generic):
        return {"t": "dtype", "v": np.dtype(v).name}
    if type(v).__name__ == "PRNGImpl":
        # random_seed/random_wrap carry the PRNG impl (a NamedTuple of
        # functions) as a param; only the registry name crosses the wire —
        # must run before the generic tuple branch.
        return {"t": "prng_impl", "v": v.name}
    for name, cls in _NAMEDTUPLES.items():
        if isinstance(v, cls):
            return {"t": "namedtuple", "cls": name,
                    "v": [encode_value(x) for x in tuple(v)]}
    for name, cls in _ENUMS.items():
        if isinstance(v, cls):
            return {"t": "enum", "cls": name, "v": v.name}
    if isinstance(v, enum.Enum):
        return {"t": "enum_str", "cls": type(v).__name__, "v": str(v.name)}
    if isinstance(v, tuple):
        return {"t": "tuple", "v": [encode_value(x) for x in v]}
    if isinstance(v, list):
        return {"t": "list", "v": [encode_value(x) for x in v]}
    if isinstance(v, dict):
        return {"t": "dict",
                "v": [[encode_value(k), encode_value(x)]
                      for k, x in v.items()]}
    if isinstance(v, (np.ndarray, jax.Array)):
        return _enc_array(v)
    if isinstance(v, jexcore.ClosedJaxpr):
        return {"t": "closed_jaxpr", "v": _encode_closed(v)}
    if isinstance(v, _core.Jaxpr):
        return {"t": "jaxpr", "v": _encode_jaxpr(v)}
    if v is jax.dtypes.float0:
        return {"t": "float0"}
    if type(v).__name__ == "UnspecifiedValue":  # jax sharding sentinel
        return {"t": "unspecified"}
    if (type(v).__name__ in ("Mesh", "AbstractMesh")
            and not getattr(v, "axis_names", None)):
        return {"t": "empty_mesh"}  # trace-context mesh placeholder
    if type(v).__name__ in ("Mesh", "AbstractMesh"):
        # shard_map's mesh: axis structure crosses the wire; the RECEIVER
        # materialises a concrete Mesh over its own devices (device handles
        # are process-local, exactly like the reference's device_assignment
        # re-resolution on the server, virtual_client.cc). AbstractMesh
        # (e.g. the Manual-typed mesh inside sharding_constraint params of
        # a shard_map body) stays abstract.
        return {"t": "mesh",
                "abstract": type(v).__name__ == "AbstractMesh",
                "axis_names": [str(n) for n in v.axis_names],
                "axis_types": [t.name for t in (v.axis_types or ())],
                "shape": [int(s) for s in v.axis_sizes]}
    if type(v).__name__ == "NamedSharding":
        return {"t": "named_sharding",
                "mesh": encode_value(v.mesh),
                "spec": encode_value(v.spec)}
    if type(v).__name__ == "PartitionSpec":
        return {"t": "pspec",
                "v": [None if e is None else
                      list(e) if isinstance(e, tuple) else str(e)
                      for e in tuple(v)]}
    if isinstance(v, frozenset):
        return {"t": "frozenset", "v": sorted(encode_value(x) for x in v)}
    if type(v).__name__ == "PyTreeDef":
        return {"t": "treedef", "v": _enc_treedef(v)}
    if isinstance(v, _core.AbstractValue):
        # Avals appear as params of pallas_call (out_avals, GridMapping's
        # index_map/scratch avals, BlockMapping array/block avals).
        return {"t": "aval", "v": _aval_dict(v)}
    for cls in (_pl_core.Blocked, _pl_core.Element, _pl_core.Squeezed):
        if isinstance(v, cls):
            return {"t": "pl_dim", "cls": cls.__name__,
                    "v": [encode_value(getattr(v, f.name))
                          for f in _dc.fields(cls)]}
    for cls in (_pl_core.BlockMapping, _pl_core.GridMapping):
        if isinstance(v, cls):
            return {"t": "pl_" + cls.__name__.lower(),
                    "v": {f.name: encode_value(getattr(v, f.name))
                          for f in _dc.fields(cls)}}
    if isinstance(v, _FrozenDict):
        return {"t": "pl_frozendict",
                "v": [[encode_value(k), encode_value(x)]
                      for k, x in dict(v).items()]}
    raise TypeError(
        f"cannot serialize param value of type {type(v).__name__}: {v!r}")


def decode_value(v: Any) -> Any:
    if not isinstance(v, dict):
        return v
    t = v["t"]
    if t == "dtype":
        return np.dtype(v["v"])
    if t == "prng_impl":
        from jax._src import prng as _prng
        return _prng.prngs[v["v"]]
    if t == "ndarray":
        return _dec_array(v)
    if t == "namedtuple":
        cls = _NAMEDTUPLES[v["cls"]]
        return cls(*[decode_value(x) for x in v["v"]])
    if t == "enum":
        return _ENUMS[v["cls"]][v["v"]]
    if t == "enum_str":
        raise TypeError(f"opaque enum {v['cls']}.{v['v']} not reconstructible")
    if t == "tuple":
        return tuple(decode_value(x) for x in v["v"])
    if t == "list":
        return [decode_value(x) for x in v["v"]]
    if t == "dict":
        return {decode_value(k): decode_value(x) for k, x in v["v"]}
    if t == "closed_jaxpr":
        return _decode_closed(v["v"])
    if t == "jaxpr":
        return _decode_jaxpr_struct(v["v"])
    if t == "float0":
        return jax.dtypes.float0
    if t == "unspecified":
        from jax._src.sharding_impls import UNSPECIFIED
        return UNSPECIFIED
    if t == "empty_mesh":
        from jax.sharding import AbstractMesh
        return AbstractMesh((), ())
    if t == "mesh":
        from jax.sharding import Mesh
        type_names = v.get("axis_types") or []
        if type_names:
            from jax.sharding import AxisType
            types = tuple(AxisType[n] for n in type_names)
        else:
            types = None
        n = 1
        for s in v["shape"]:
            n *= s
        devs = jax.devices()
        if len(devs) < n:
            raise ValueError(
                f"received mesh needs {n} devices, host has {len(devs)}")
        kwargs = {} if types is None else {"axis_types": types}
        mesh = Mesh(np.array(devs[:n]).reshape(v["shape"]),
                    axis_names=tuple(v["axis_names"]), **kwargs)
        if v.get("abstract"):
            # Derive from the concrete local mesh so device_kind/num_cores
            # match the avals the receiver's own trace machinery produces
            # (AbstractMesh equality includes them).
            return mesh.abstract_mesh
        return mesh
    if t == "named_sharding":
        from jax.sharding import NamedSharding
        return NamedSharding(decode_value(v["mesh"]),
                             decode_value(v["spec"]))
    if t == "pspec":
        from jax.sharding import PartitionSpec
        return PartitionSpec(*[
            None if e is None else tuple(e) if isinstance(e, list) else e
            for e in v["v"]])
    if t == "frozenset":
        return frozenset(decode_value(x) for x in v["v"])
    if t == "treedef":
        return _dec_treedef(v["v"])
    if t == "aval":
        return _make_aval(v["v"])
    if t == "pl_dim":
        cls = getattr(_pl_core, v["cls"])
        return cls(*[decode_value(x) for x in v["v"]])
    if t in ("pl_blockmapping", "pl_gridmapping"):
        cls = (_pl_core.BlockMapping if t == "pl_blockmapping"
               else _pl_core.GridMapping)
        return cls(**{k: decode_value(x) for k, x in v["v"].items()})
    if t == "pl_frozendict":
        return _FrozenDict(
            {decode_value(k): decode_value(x) for k, x in v["v"]})
    raise TypeError(f"unknown tag {t}")


# --------------------------------------------------------------------------
# Jaxpr encoding
# --------------------------------------------------------------------------

def _aval_dict(aval) -> dict:
    if type(aval).__name__ == "AbstractRef":
        # Pallas/state Ref avals (kernel operands, scratch): inner aval +
        # memory space. The memory space is a pallas MemorySpace enum (or
        # None = default), encoded by name.
        ms = aval.memory_space
        return {"ref": _aval_dict(aval.inner_aval),
                "memory_space": None if ms is None else encode_value(ms)}
    if jax.dtypes.issubdtype(aval.dtype, jax.dtypes.extended):
        # PRNG-key avals (key<fry> etc.): encode the impl name; _make_aval
        # rebuilds the KeyTy dtype from the live impl registry.
        dt = "key:" + _keyimpl_name(aval.dtype)
    elif aval.dtype == jax.dtypes.float0:
        dt = "float0"
    else:
        dt = np.dtype(aval.dtype).name
    d = {
        "shape": list(aval.shape),
        "dtype": dt,
        "weak_type": bool(getattr(aval, "weak_type", False)),
    }
    vma = getattr(aval, "vma", None)
    if vma:
        # Varying-manual-axes typing inside shard_map bodies: without it
        # the rebuilt jaxpr fails check_vma on bind.
        d["vma"] = sorted(str(a) for a in vma)
    shd = getattr(aval, "sharding", None)
    if shd is not None and not getattr(shd.mesh, "empty", True):
        # An aval carrying vma MUST also carry the sharding whose (manual
        # abstract) mesh licenses those axes — get_vma rejects vma against
        # an empty mesh.
        d["sharding"] = encode_value(shd)
    return d


def _make_aval(d: dict):
    if "ref" in d:
        from jax._src.state.types import AbstractRef
        ms = d.get("memory_space")
        ms = None if ms is None else decode_value(ms)
        return AbstractRef(_make_aval(d["ref"]), ms)
    if d["dtype"] == "float0":
        return _core.ShapedArray(tuple(d["shape"]), jax.dtypes.float0)
    kw = {}
    if d.get("sharding"):
        kw["sharding"] = decode_value(d["sharding"])
    if d.get("vma"):
        kw["vma"] = frozenset(d["vma"])
    dtype = (_key_dtype(d["dtype"][4:]) if d["dtype"].startswith("key:")
             else np.dtype(d["dtype"]))
    return _core.ShapedArray(tuple(d["shape"]), dtype,
                             weak_type=d.get("weak_type", False), **kw)


def _encode_jaxpr(jaxpr) -> dict:
    var_ids: Dict[Any, int] = {}

    def vid(v) -> int:
        if v not in var_ids:
            var_ids[v] = len(var_ids)
        return var_ids[v]

    def enc_atom(a):
        if isinstance(a, jexcore.Literal):
            return {"k": "lit", "v": _enc_array(a.val),
                    "aval": _aval_dict(a.aval)}
        return {"k": "var", "id": vid(a), "aval": _aval_dict(a.aval)}

    eqns = []
    for eqn in jaxpr.eqns:
        outvars = []
        for ov in eqn.outvars:
            if type(ov).__name__ == "DropVar":
                outvars.append({"k": "drop", "aval": _aval_dict(ov.aval)})
            else:
                outvars.append(enc_atom(ov))
        e = {
            "prim": eqn.primitive.name,
            "invars": [enc_atom(a) for a in eqn.invars],
            "outvars": outvars,
            "params": {k: encode_value(v) for k, v in eqn.params.items()},
        }
        # Equations traced inside shard_map record the ambient manual mesh
        # in their JaxprEqnContext; vma checking at re-bind (scan carry
        # harmonisation etc.) consults it, so it must cross the wire.
        ctx_mesh = getattr(getattr(eqn, "ctx", None), "cur_abstract_mesh",
                           None)
        if ctx_mesh is not None and getattr(ctx_mesh, "axis_names", ()):
            e["ctx_mesh"] = encode_value(ctx_mesh)
        eqns.append(e)
    return {
        "constvars": [enc_atom(v) for v in jaxpr.constvars],
        "invars": [enc_atom(v) for v in jaxpr.invars],
        "outvars": [enc_atom(a) for a in jaxpr.outvars],
        "eqns": eqns,
    }


def _decode_jaxpr_struct(d: dict):
    env: Dict[int, Any] = {}

    def dec_var(a):
        i = a["id"]
        if i not in env:
            env[i] = jexcore.Var(_make_aval(a["aval"]))
        return env[i]

    def dec_atom(a):
        if a["k"] == "lit":
            val = _dec_array(a["v"])
            aval = _make_aval(a["aval"])
            if jax.dtypes.issubdtype(aval.dtype, jax.dtypes.extended):
                # Typed-key literal: _dec_array already rebuilt the jax
                # key array; np casting does not apply.
                return jexcore.Literal(val, aval)
            if not aval.shape:
                val = val.reshape(())
                # scalars come back as 0-d arrays; Literal accepts those
            return jexcore.Literal(
                np.asarray(val, dtype=aval.dtype), aval)
        return dec_var(a)

    constvars = [dec_atom(a) for a in d["constvars"]]
    invars = [dec_atom(a) for a in d["invars"]]
    eqns = []
    for e in d["eqns"]:
        prim = primitive_by_name(e["prim"])
        inv = [dec_atom(a) for a in e["invars"]]
        outv = []
        for a in e["outvars"]:
            if a["k"] == "drop":
                outv.append(_core.DropVar(_make_aval(a["aval"])))
            else:
                outv.append(dec_atom(a))
        params = {k: decode_value(v) for k, v in e["params"].items()}
        if prim.name == "pallas_call":
            # The `interpret` flag is a property of the EXECUTING backend,
            # not the program: a kernel traced on TPU must run in interpret
            # mode on a CPU server (tests, virtual meshes) and vice versa.
            params["interpret"] = jax.default_backend() == "cpu"
        ctx = None
        if "ctx_mesh" in e:
            import jax as _jax
            ctx = _core.JaxprEqnContext(
                None, bool(_jax.config.jax_threefry_partitionable))
            # The constructor snapshots the AMBIENT abstract mesh; restore
            # the recorded one (the manual mesh this eqn was traced under).
            ctx.cur_abstract_mesh = decode_value(e["ctx_mesh"])
        # Recompute the eqn's effects (Ref read/write effects inside pallas
        # kernels, and their propagation through while/scan/cond/jit):
        # effects aren't serialized — abstract_eval re-derives them from the
        # decoded avals+params. Only prims that can actually carry effects
        # are re-evaluated: effect-free lax prims keep no_effects without
        # paying abstract_eval (scan/shard_map bodies are expensive), and a
        # genuine decode error in a plain prim can't hide behind a blanket
        # except here.
        effects = _core.no_effects
        if _may_carry_effects(prim, params):
            try:
                out = prim.abstract_eval(*[x.aval for x in inv], **params)
                if isinstance(out, tuple) and len(out) == 2:
                    effects = out[1]
            except Exception as exc:
                log.debug("effects re-derivation failed for %s: %s",
                          prim.name, exc)
        eqns.append(_core.new_jaxpr_eqn(
            inv, outv, prim, params, effects=effects, ctx=ctx))
    outvars = [dec_atom(a) for a in d["outvars"]]
    import warnings
    with warnings.catch_warnings():
        # Deserialized jaxprs have no source program to point DebugInfo at;
        # jax's default placeholder is exactly right here.
        warnings.simplefilter("ignore", DeprecationWarning)
        # The jaxpr-level effects are the union of its eqns' (jax invariant)
        # — required so _may_carry_effects sees nested effects through
        # sub-jaxpr params instead of re-running abstract_eval everywhere.
        effects = _core.join_effects(*[e.effects for e in eqns])
        return _core.Jaxpr(constvars=constvars, invars=invars,
                           outvars=outvars, eqns=eqns, effects=effects)


def _encode_closed(closed) -> dict:
    return {
        "jaxpr": _encode_jaxpr(closed.jaxpr),
        "consts": [encode_value(c if _is_key_array(c) else np.asarray(c))
                   for c in closed.consts],
    }


def _decode_closed(d: dict):
    jaxpr = _decode_jaxpr_struct(d["jaxpr"])
    consts = [decode_value(c) for c in d["consts"]]
    return jexcore.ClosedJaxpr(jaxpr, consts)


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

def serialize_closed_jaxpr(closed, inline: bool = True) -> bytes:
    """ClosedJaxpr -> wire bytes (inlines call primitives first)."""
    if inline:
        from tepdist_tpu.graph.jaxpr_graph import inline_calls
        jaxpr = inline_calls(closed.jaxpr)
        closed = jexcore.ClosedJaxpr(jaxpr, closed.consts)
    return json.dumps(_encode_closed(closed)).encode()


def deserialize_closed_jaxpr(data: bytes):
    # ``data`` may be a zero-copy memoryview blob (rpc/protocol.unpack).
    return _decode_closed(json.loads(bytes(data).decode()))


def serialize_pytree_leaves(tree) -> Tuple[bytes, Any]:
    """Flatten a pytree of arrays -> (bytes, treedef) for literal transfer
    (reference: TransferToServerHost raw-bytes path)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    payload = [encode_value(l if _is_key_array(l) else np.asarray(l))
               for l in leaves]
    return json.dumps(payload).encode(), treedef


def deserialize_leaves(data: bytes) -> List[np.ndarray]:
    return [decode_value(d) for d in json.loads(bytes(data).decode())]
