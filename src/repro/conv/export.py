"""AOT-exported plan artifacts for fleet cold-start (``repro.conv.export``).

Serving a model on a fresh worker normally re-pays the whole plan
lifecycle per process: plan every layer, transform every kernel, trace
and compile every (layer x bucket) jit.  The paper's pipeline wins by
doing all layout decisions ONCE and amortizing them; this module extends
that amortization across the fleet:

    net = plan_network(layers, ...)
    net.export("vgg.rpa", params=kernels, weights_version=7)   # build once

    # on a fresh worker: zero re-planning, zero re-tracing
    loaded = load_network("vgg.rpa")
    y = loaded["conv1"](x, bias=b)                             # deploy many

An artifact is a single zip file holding, per (net, layer):

  ``manifest.json``    format/jax/device-kind/mesh compatibility stamps,
                       the full resolved plan config (enough to re-plan
                       live), the ``weights_version``, and a plan-lint
                       ``PlanProfile`` fingerprint per layer.
  ``fns/<hash>.bin``   the ``jax.export`` serialized StableHLO module
                       (deduplicated across same-plan layers/buckets).
  ``exe/<hash>.pkl``   the XLA *executable* for that module
                       (``jax.experimental.serialize_executable``) —
                       zero-compile rehydration on an identical worker.
  ``.../state<i>.npy`` the prepared kernel slabs (stage-2 output in the
                       exact layout the schedule consumes).
  ``.../kernel.npy``   the raw kernel, so an incompatible worker can
                       still fall back to live planning.

``load_network`` validates device-kind / jax-version / mesh-shape
compatibility; compatible artifacts rehydrate native executables first
(no tracing, no XLA compile), per-layer falling back to the portable
StableHLO module (no tracing, one compile).  On a compatibility mismatch
it warns and falls back to live planning from the stored configs +
kernels (``on_mismatch="error"`` raises instead).
``verify`` re-derives every fingerprint from a live re-plan and compares
against the export-time stamps — the plan-lint certificate that the
artifact executes the same schedule it was built from.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import io
import json
import os
import pickle
import warnings
import zipfile
from typing import Any, Mapping, Optional

from repro.conv.epilogue import Epilogue

ARTIFACT_VERSION = 2

# The PlanProfile facts a fingerprint certifies: everything structural
# about the schedule (backend/schedule/collectives/stage ops/overlap/
# epilogue/precision), nothing measured or byte-counted.
FINGERPRINT_FIELDS = (
    "backend", "schedule", "prepared", "collectives", "stage_counts",
    "overlap", "num_slabs", "epilogue", "compute_dtype", "cgemm_dtypes",
)


class ArtifactMismatch(RuntimeError):
    """The artifact cannot be used as-is on this worker."""


# --------------------------------------------------------------------------
# Fingerprints (plan-lint certificate)
# --------------------------------------------------------------------------

def plan_fingerprint(plan, *, prepared: bool = False) -> str:
    """sha256 over the canonical structural subset of the plan's
    ``PlanProfile`` (``FINGERPRINT_FIELDS``).  Stable across processes on
    one jax version, so a fresh worker can certify an artifact by
    re-planning live and comparing."""
    prof = plan.analyze(prepared=prepared).to_dict()
    payload = {k: prof.get(k) for k in FINGERPRINT_FIELDS}
    blob = json.dumps(payload, sort_keys=True, default=str).encode()
    return "sha256:" + hashlib.sha256(blob).hexdigest()


# --------------------------------------------------------------------------
# Plan config (de)serialization — enough to re-plan live
# --------------------------------------------------------------------------

def _dtype_name(dt) -> Optional[str]:
    if dt is None:
        return None
    import numpy as np
    return np.dtype(dt).name


def _mesh_config(mesh) -> Optional[dict]:
    if mesh is None:
        return None
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(s) for s in mesh.devices.shape]}


def _rebuild_mesh(cfg: Optional[dict]):
    if cfg is None:
        return None
    import jax
    from repro.compat import make_mesh
    need = 1
    for s in cfg["shape"]:
        need *= int(s)
    if need > len(jax.devices()):
        raise ArtifactMismatch(
            f"artifact mesh {tuple(cfg['shape'])} needs {need} devices, "
            f"this worker has {len(jax.devices())}")
    return make_mesh(tuple(int(s) for s in cfg["shape"]),
                     tuple(cfg["axis_names"]))


def plan_config(plan) -> dict:
    """JSON-able resolved plan config; ``rebuild_plan`` inverts it."""
    return {
        "x_shape": list(plan.x_shape),
        "k_shape": list(plan.k_shape),
        "padding": list(plan.padding),
        "stride": int(plan.spec.stride),
        "delta": int(plan.spec.delta),
        "backend": plan.backend,
        "schedule": plan.schedule,
        "three_m": bool(plan.three_m),
        "bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
        "dft_bt": plan.dft_bt,
        "compute_dtype": _dtype_name(plan.compute_dtype),
        "mesh": _mesh_config(plan.mesh),
        "data_axis": plan.data_axis,
        "model_axis": plan.model_axis,
        "replicate_kernel_transform": bool(plan.replicate_kernel_transform),
        "epilogue": {"bias": plan.epilogue.bias,
                     "activation": plan.epilogue.activation,
                     "residual": plan.epilogue.residual},
        "overlap": plan.overlap,
    }


def rebuild_plan(cfg: dict):
    """Re-plan live from a stored config (the fallback path).  Raises
    ``ArtifactMismatch`` when the mesh cannot be rebuilt here."""
    import numpy as np
    from repro.conv.plan import plan_conv
    mesh = _rebuild_mesh(cfg.get("mesh"))
    cd = cfg.get("compute_dtype")
    return plan_conv(
        tuple(cfg["x_shape"]), tuple(cfg["k_shape"]),
        padding=tuple(cfg["padding"]), delta=int(cfg["delta"]),
        stride=int(cfg.get("stride", 1)),
        backend=cfg["backend"], schedule=cfg["schedule"], mesh=mesh,
        three_m=cfg["three_m"], bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
        dft_bt=cfg["dft_bt"],
        compute_dtype=None if cd is None else np.dtype(cd),
        data_axis=cfg["data_axis"], model_axis=cfg["model_axis"],
        replicate_kernel_transform=cfg["replicate_kernel_transform"],
        epilogue=Epilogue(**cfg["epilogue"]),
        overlap=cfg["overlap"])


# --------------------------------------------------------------------------
# The exported callable per layer
# --------------------------------------------------------------------------

def _layer_fn(plan, *, prepared: bool, treedef, n_state: int):
    """The function ``jax.export`` lowers for one layer.

    Prepared: ``fn(x, *state_leaves, [bias], [residual])`` — stages
    1/3/4 against the baked slab layout.  Unprepared:
    ``fn(x, k, [bias], [residual])`` — the full pipeline.  Epilogue
    operands stay runtime arguments so an artifact serves any bias/
    residual values without re-export."""
    import jax
    from repro.conv import registry
    be = registry.get_backend(plan.backend)
    ep = plan.epilogue

    def fn(x, *args):
        state = jax.tree_util.tree_unflatten(treedef, list(args[:n_state]))
        ops = args[n_state:]
        bias = residual = None
        i = 0
        if ep.bias:
            bias = ops[i]
            i += 1
        if ep.residual:
            residual = ops[i]
        if be.pipeline_factory is not None:
            pipe = be.make_pipeline(plan)
            if prepared:
                return pipe.execute(plan, x, state, bias=bias,
                                    residual=residual)
            return pipe.full(plan, x, state, bias=bias, residual=residual)
        if not ep.is_noop:
            return be.execute(plan, x, state, bias=bias, residual=residual)
        return be.execute(plan, x, state)

    return fn


def _np_bytes(arr) -> bytes:
    import numpy as np
    bio = io.BytesIO()
    np.save(bio, np.asarray(arr))
    return bio.getvalue()


def _np_load(data: bytes):
    import numpy as np
    return np.load(io.BytesIO(data))


def _state_format(treedef, leaves) -> str:
    import jax
    if treedef == jax.tree_util.tree_structure(leaves[0]) \
            and len(leaves) == 1:
        return "leaf"
    if treedef == jax.tree_util.tree_structure(tuple(leaves)):
        return "tuple"
    raise ValueError(
        f"unsupported prepared-state structure {treedef} (export knows "
        "flat tuples and single leaves)")


def _state_treedef(fmt: str, n: int):
    import jax
    if fmt == "leaf":
        return jax.tree_util.tree_structure(0)
    return jax.tree_util.tree_structure(tuple(range(n)))


# --------------------------------------------------------------------------
# Export
# --------------------------------------------------------------------------

def _as_net_mapping(net) -> "collections.OrderedDict":
    """Normalize NetworkPlan | BucketedNetworkPlan | Mapping[label,
    NetworkPlan] to an ordered label -> NetworkPlan mapping."""
    from repro.conv.netplan import BucketedNetworkPlan, NetworkPlan
    if isinstance(net, NetworkPlan):
        return collections.OrderedDict([("net", net)])
    if isinstance(net, BucketedNetworkPlan):
        return collections.OrderedDict(
            (f"b{b}", n) for b, n in net.items())
    return collections.OrderedDict(
        (str(label), n) for label, n in net.items())


def export_network(net, path: str, *, params: Optional[Mapping] = None,
                   weights_version=None, dtype=None) -> str:
    """Lower every (layer x net) jit through ``jax.export`` into one
    artifact file.  With ``params`` the layers export *prepared* (the
    transformed kernel slabs ride along, version-keyed); without, the
    artifact is unprepared and loaded layers take ``(x, k)``.  Returns
    ``path``."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    nets = _as_net_mapping(net)
    prepared = params is not None
    dt = jnp.float32 if dtype is None else dtype
    uses_mesh = any(p.mesh is not None
                    for n in nets.values() for p in n.plans.values())
    manifest: dict = {
        "artifact_version": ARTIFACT_VERSION,
        "jax_version": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
        "platform": jax.default_backend(),
        "nr_devices": len(jax.devices()),
        "uses_mesh": uses_mesh,
        "weights_version": weights_version,
        "prepared": prepared,
        "dtype": _dtype_name(dt),
        "nets": {},
    }
    fn_members: dict = {}            # (id(plan), prepared) -> member name
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as zf:
        for label, nplan in nets.items():
            layers: dict = {}
            for name, plan in nplan.items():
                layers[name] = _export_layer(
                    zf, f"nets/{label}/{name}", plan, name, params,
                    weights_version=weights_version, dt=dt,
                    fn_members=fn_members, jax_export=jax_export)
            manifest["nets"][label] = {"layers": layers}
        zf.writestr("manifest.json",
                    json.dumps(manifest, indent=1, sort_keys=True))
    os.replace(tmp, path)
    return path


def _export_layer(zf, member_dir, plan, name, params, *, weights_version,
                  dt, fn_members, jax_export) -> dict:
    import jax
    prepared = params is not None
    entry = dict(plan_config(plan))
    entry["fingerprint"] = plan_fingerprint(plan, prepared=prepared)
    entry["prepared"] = prepared
    entry["state"] = []
    entry["kernel"] = None
    if prepared:
        if name not in params:
            raise ValueError(f"export: params missing kernel for {name!r}")
        pc = plan.prepare(params[name], weights_version=weights_version)
        leaves, treedef = jax.tree_util.tree_flatten(pc.state)
        entry["state_format"] = _state_format(treedef, leaves)
        for i, leaf in enumerate(leaves):
            member = f"{member_dir}/state{i}.npy"
            zf.writestr(member, _np_bytes(leaf))
            entry["state"].append(member)
        kmember = f"{member_dir}/kernel.npy"
        zf.writestr(kmember, _np_bytes(params[name]))
        entry["kernel"] = kmember
        state_avals = [jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                       for v in leaves]
        n_state = len(leaves)
    else:
        treedef = jax.tree_util.tree_structure(0)
        entry["state_format"] = "leaf"
        state_avals = [jax.ShapeDtypeStruct(plan.k_shape, dt)]
        n_state = 1
    fn_key = (id(plan), prepared)
    if fn_key not in fn_members:
        fn = _layer_fn(plan, prepared=prepared, treedef=treedef,
                       n_state=n_state)
        avals = [jax.ShapeDtypeStruct(plan.x_shape, dt)] + state_avals
        if plan.epilogue.bias:
            avals.append(jax.ShapeDtypeStruct((plan.spec.Cout,), dt))
        if plan.epilogue.residual:
            avals.append(jax.ShapeDtypeStruct(plan.out_shape, dt))
        blob = jax_export.export(jax.jit(fn))(*avals).serialize()
        member = ("fns/"
                  + hashlib.sha256(blob).hexdigest()[:24] + ".bin")
        if member not in {m["fn"] for m in fn_members.values()}:
            zf.writestr(member, bytes(blob))
        fn_members[fn_key] = {"fn": member,
                              "exe": _export_exe(zf, fn, avals, member)}
    entry["fn"] = fn_members[fn_key]["fn"]
    entry["exe"] = fn_members[fn_key]["exe"]
    return entry


def _export_exe(zf, fn, avals, fn_member) -> Optional[str]:
    """Serialize the fully compiled XLA executable next to the portable
    module (best-effort: ``None`` when the backend cannot serialize
    executables).  The exe is device-kind/device-count specific — exactly
    the compatibility the manifest already gates on."""
    import jax
    try:
        from jax.experimental import serialize_executable as se
        compiled = jax.jit(fn).lower(*avals).compile()
        payload, in_tree, out_tree = se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
    except Exception:
        return None
    member = "exe/" + fn_member[len("fns/"):-len(".bin")] + ".pkl"
    if member not in zf.namelist():
        zf.writestr(member, blob)
    return member


# --------------------------------------------------------------------------
# Load
# --------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class LoadedConv:
    """One rehydrated layer: the deserialized AOT module plus its baked
    slabs, callable with the same convention as ``PreparedConv``
    (prepared: ``layer(x, bias=..., residual=...)``) or ``ConvPlan``
    (unprepared: ``layer(x, k, bias=...)``).  ``native`` means the call
    dispatches a deserialized XLA executable directly — zero compile,
    but eager-only (a ``Compiled`` cannot be traced through an outer
    ``jit``); non-native layers wrap the portable StableHLO module in
    ``jit`` and compose freely."""
    name: str
    config: dict
    fingerprint: str
    prepared: bool
    epilogue: Epilogue
    state: tuple
    _call: Any
    native: bool = False

    @property
    def x_shape(self) -> tuple:
        return tuple(self.config["x_shape"])

    @property
    def k_shape(self) -> tuple:
        return tuple(self.config["k_shape"])

    def __call__(self, x, *args, bias=None, residual=None):
        ep = self.epilogue
        if self.prepared:
            if args:
                raise TypeError(
                    f"prepared loaded layer {self.name!r} takes only x "
                    "(the kernel is baked into the artifact)")
            ops = []
        else:
            if len(args) != 1:
                raise TypeError(
                    f"unprepared loaded layer {self.name!r} takes (x, k)")
            ops = [args[0]]
        if ep.bias != (bias is not None):
            raise ValueError(
                f"layer {self.name!r} epilogue declares bias={ep.bias} "
                f"but bias {'was not' if ep.bias else 'was'} passed")
        if ep.residual != (residual is not None):
            raise ValueError(
                f"layer {self.name!r} epilogue declares residual="
                f"{ep.residual} but residual "
                f"{'was not' if ep.residual else 'was'} passed")
        if bias is not None:
            ops.append(bias)
        if residual is not None:
            ops.append(residual)
        return self._call(x, *ops)


@dataclasses.dataclass(frozen=True, eq=False)
class LoadedNetwork:
    """A rehydrated network: Mapping-like over loaded layers, duck-typed
    to ``PreparedNetwork``.  ``source`` is ``"aot"`` (zero-retrace AOT
    modules) or ``"live"`` (the fallback re-planned this artifact)."""
    layers: "collections.OrderedDict"
    weights_version: Any
    source: str
    fingerprints: dict

    def __getitem__(self, name):
        return self.layers[name]

    def __iter__(self):
        return iter(self.layers)

    def __len__(self):
        return len(self.layers)

    def items(self):
        return self.layers.items()

    @property
    def x_shape(self) -> tuple:
        first = next(iter(self.layers.values()))
        if hasattr(first, "x_shape"):
            return tuple(first.x_shape)
        return tuple(first.plan.x_shape)


def read_manifest(path: str) -> dict:
    with zipfile.ZipFile(path) as zf:
        return json.loads(zf.read("manifest.json"))


def compat_reasons(manifest: dict) -> list:
    """Why this artifact cannot run AOT on this worker ([] = compatible):
    format version, jax version, device kind, and — for sharded plans —
    the device count the meshes were laid out for."""
    import jax
    reasons = []
    if manifest.get("artifact_version") != ARTIFACT_VERSION:
        reasons.append(
            f"artifact format v{manifest.get('artifact_version')} != "
            f"v{ARTIFACT_VERSION}")
    if manifest.get("jax_version") != jax.__version__:
        reasons.append(f"jax {manifest.get('jax_version')} != "
                       f"{jax.__version__}")
    kind = jax.devices()[0].device_kind
    if manifest.get("device_kind") != kind:
        reasons.append(f"device kind {manifest.get('device_kind')!r} != "
                       f"{kind!r}")
    if manifest.get("uses_mesh") and \
            manifest.get("nr_devices") != len(jax.devices()):
        reasons.append(f"mesh laid out for {manifest.get('nr_devices')} "
                       f"devices, worker has {len(jax.devices())}")
    return reasons


def _aot_call(exported, state):
    import jax

    def run(x, *ops):
        return exported.call(x, *state, *ops)

    return jax.jit(run)


def _load_exe(zf, member, cache):
    """Deserialize a native executable member (memoized per load); None
    when the blob does not rehydrate on this worker."""
    if member not in cache:
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = pickle.loads(zf.read(member))
            cache[member] = se.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception:
            cache[member] = None
    return cache[member]


def _load_layer_aot(zf, name, entry, exe_cache) -> LoadedConv:
    import jax.numpy as jnp
    from jax import export as jax_export
    state = tuple(jnp.asarray(_np_load(zf.read(m)))
                  for m in entry["state"])
    loaded = _load_exe(zf, entry["exe"], exe_cache) \
        if entry.get("exe") else None
    if loaded is not None:
        def call(x, *ops, _exe=loaded, _state=state):
            return _exe(x, *_state, *ops)
        native = True
    else:
        exported = jax_export.deserialize(bytearray(zf.read(entry["fn"])))
        call = _aot_call(exported, state)
        native = False
    return LoadedConv(
        name=name, config=entry, fingerprint=entry["fingerprint"],
        prepared=entry["prepared"], epilogue=Epilogue(**entry["epilogue"]),
        state=state, _call=call, native=native)


def _load_layer_live(zf, name, entry, weights_version):
    import jax.numpy as jnp
    plan = rebuild_plan(entry)
    if entry["prepared"]:
        k = jnp.asarray(_np_load(zf.read(entry["kernel"])))
        return plan.prepare(k, weights_version=weights_version)
    return plan


def load_network(path: str, *, on_mismatch: str = "fallback"):
    """Rehydrate an artifact on this worker.

    Compatible artifacts load as AOT modules — zero re-planning, zero
    re-tracing, zero kernel re-transforms.  Incompatible ones (other jax
    version / device kind / device count) fall back to live planning
    from the stored configs + kernels with a warning
    (``on_mismatch="error"`` raises ``ArtifactMismatch`` instead).

    Returns a ``LoadedNetwork`` for single-net artifacts, else an
    ``OrderedDict[label, LoadedNetwork]`` (bucketed exports)."""
    if on_mismatch not in ("fallback", "error"):
        raise ValueError(f"unknown on_mismatch {on_mismatch!r}")
    manifest = read_manifest(path)
    reasons = compat_reasons(manifest)
    if reasons:
        if on_mismatch == "error":
            raise ArtifactMismatch(
                f"plan artifact {path!r} incompatible: "
                + "; ".join(reasons))
        warnings.warn(
            f"plan artifact {path!r} incompatible ({'; '.join(reasons)}); "
            "falling back to live planning", stacklevel=2)
    source = "live" if reasons else "aot"
    wv = manifest.get("weights_version")
    out: "collections.OrderedDict" = collections.OrderedDict()
    exe_cache: dict = {}
    with zipfile.ZipFile(path) as zf:
        for label, ncfg in manifest["nets"].items():
            layers: "collections.OrderedDict" = collections.OrderedDict()
            fps = {}
            for name, entry in ncfg["layers"].items():
                fps[name] = entry["fingerprint"]
                if source == "aot":
                    layers[name] = _load_layer_aot(zf, name, entry,
                                                   exe_cache)
                else:
                    layers[name] = _load_layer_live(zf, name, entry, wv)
            out[label] = LoadedNetwork(layers=layers, weights_version=wv,
                                       source=source, fingerprints=fps)
    if list(out) == ["net"]:
        return out["net"]
    return out


def verify(path: str) -> dict:
    """Plan-lint certificate: re-plan every stored layer config LIVE on
    this worker, recompute its ``PlanProfile`` fingerprint, and compare
    against the export-time stamp.  Returns ``{"ok": bool, "n_checked":
    int, "mismatches": [...]}``.  (Re-planning hits the plan cache /
    static analyzer only — nothing executes.)"""
    manifest = read_manifest(path)
    mismatches = []
    n = 0
    for label, ncfg in manifest["nets"].items():
        for name, entry in ncfg["layers"].items():
            n += 1
            plan = rebuild_plan(entry)
            fp = plan_fingerprint(plan, prepared=entry["prepared"])
            if fp != entry["fingerprint"]:
                mismatches.append(
                    {"net": label, "layer": name,
                     "exported": entry["fingerprint"], "live": fp})
    return {"ok": not mismatches, "n_checked": n,
            "mismatches": mismatches}
