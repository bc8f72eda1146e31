"""Versioned posterior-state AND incremental-store persistence (npz).

Serving fleets replicate by shipping ``PosteriorState`` pytrees, not data:
a state is a few small dense factors (|S|-space for the summary methods,
R-space for pICF), so a fitted/streamed posterior can be checkpointed on one
process and restored bit-for-bit on another (``GPServer.swap_from_checkpoint``
hot-swaps it under live traffic with zero recompilation when shapes match).

Format: one ``.npz`` per state. ``__schema__`` guards the container layout,
``__state__`` names the registered NamedTuple type, and every field is
stored as its own array under ``field:<name>`` — NumPy round-trips array
bits exactly, so ``load_state(save_state(p, s)) == s`` bitwise, dtypes
included (float64 fields need x64 enabled on load, as everywhere else).

The registry is keyed by type NAME, so any module can add its own state via
``register_state`` and the loader stays closed over registered types —
unknown or field-mismatched files fail loudly instead of mis-assembling.

``save_store``/``load_store`` persist the incremental STORES themselves
(``online.PITCStore``/``online.PICStore``/``picf.PICFStore``): the
per-machine summary factors, pPIC block caches, and the pICF pivot basis —
everything the Sec. 5.2 update algebra is closed over. A state checkpoint
lets a restarted process SERVE; a store checkpoint lets it keep
ASSIMILATING. Arrays round-trip bitwise under their own schema tag
(``__store_schema__``); the two non-array store members are encoded as
metadata — the kernel by registry name / ``KernelSpec`` fields, the runner
by mode + machine count — and anything unencodable (a bespoke kernel
closure, a ``ShardMapRunner`` whose mesh is process-local) must be
re-supplied via the ``kfn=``/``runner=`` overrides at load time, failing
loudly otherwise.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import zipfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api, linalg
from repro.core import covariance as cov

# v2: the pPITC/pPIC global factor is kept whitened by Kss_L (api.PITCState
# A_L/w, the stores' G/A_L) and pPIC blocks cache V = Kss_L⁻¹ K_SD. v1 files
# still load: the types whose layout changed are converted on load
# (``_STATE_V1``/``_STORE_V1``, not bitwise), the others are unchanged.
SCHEMA_VERSION = 2
STORE_SCHEMA_VERSION = 2

_FIELD = "field:"


class CheckpointError(ValueError):
    """A checkpoint file cannot be trusted: missing, truncated, corrupt, or
    failing its embedded per-field checksums. Carries the offending ``path``
    and a human ``reason`` — the serving runtime's revive path keys on this
    (a corrupt artifact must be DETECTED, never loaded into a tenant)."""

    def __init__(self, path, reason: str):
        self.path = str(path)
        self.reason = reason
        super().__init__(f"{self.path}: {reason}")


@contextlib.contextmanager
def _checkpoint_io(path, kind: str):
    """Translate the raw failure modes of reading an npz — zipfile CRC/central-
    directory errors on truncated or bit-flipped files, ``KeyError`` on
    missing entries, NumPy header ``ValueError``s — into one CheckpointError
    with the path attached. Our own CheckpointErrors pass through."""
    try:
        yield
    except CheckpointError:
        raise
    except FileNotFoundError as e:
        raise CheckpointError(path, f"no such {kind}") from e
    except (zipfile.BadZipFile, EOFError, KeyError, OSError, ValueError) as e:
        raise CheckpointError(
            path, f"truncated or corrupt {kind} "
                  f"({type(e).__name__}: {e})") from e


def _crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def _checksum_meta(payload: dict) -> np.str_:
    return np.str_(json.dumps({k: _crc(v) for k, v in payload.items()}))


def _verify_checksums(path, z, arrays: dict) -> None:
    """Check materialized arrays against the embedded ``__checksums__`` map
    (absent on pre-checksum checkpoints: nothing to verify). The zip layer
    already CRCs each entry's bytes; this additionally pins the DECODED
    array content, so a checkpoint that unzips cleanly but decodes to the
    wrong bits (header tampering, partial rewrite) still fails loudly."""
    if "__checksums__" not in z.files:
        return
    want = json.loads(str(z["__checksums__"]))
    for k, a in arrays.items():
        if k in want and _crc(a) != want[k]:
            raise CheckpointError(
                path, f"checksum mismatch for {k!r} (file is corrupt — "
                      f"expected crc {want[k]}, got {_crc(a)})")

STATE_TYPES: dict[str, type] = {}


def register_state(cls: type) -> type:
    """Register a NamedTuple state type for save/load by name."""
    if not hasattr(cls, "_fields"):
        raise TypeError(f"{cls!r} is not a NamedTuple state type")
    STATE_TYPES[cls.__name__] = cls
    return cls


for _cls in (api.FGPState, api.PITCState, api.PICState, api.PICFState):
    register_state(_cls)


def _tri(L, B):
    return jax.scipy.linalg.solve_triangular(L, B, lower=True)


def _whiten_v1(a: dict, L_key: str, Sdd_key: str) -> dict:
    """v1 -> v2 global factor: A_L = Kss_L⁻¹ Sdd_L (lower triangular with a
    positive diagonal, and A_L A_Lᵀ = Kss_L⁻¹ Σ̈ Kss_L⁻ᵀ)."""
    a[Sdd_key.replace("Sdd_L", "A_L")] = _tri(a[L_key], a.pop(Sdd_key))
    return a


@linalg.f32_matmuls
def _pitc_state_v1(a: dict) -> dict:
    a = _whiten_v1(a, "Kss_L", "Sdd_L")
    # alpha = Σ̈⁻¹ÿ  ->  w = A_L⁻¹ Kss_L⁻¹ ÿ = A_Lᵀ Kss_Lᵀ alpha
    a["w"] = a["A_L"].T @ (a["Kss_L"].T @ a.pop("alpha"))
    return a


def _pic_state_v1(a: dict) -> dict:
    for k in ("ydot", "beta", "B", "Sdot"):
        del a[k]
    a["V"] = jax.vmap(lambda K: _tri(a["Kss_L"], K))(a.pop("Ksd"))
    return _pitc_state_v1(a)


_STATE_V1 = {"PITCState": _pitc_state_v1, "PICState": _pic_state_v1}


def save_state(path, state) -> pathlib.Path:
    """Write a registered PosteriorState to ``path`` (npz). Returns the
    path actually written (always exactly ``path`` — no implicit .npz
    suffix surprises)."""
    name = type(state).__name__
    if name not in STATE_TYPES:
        raise ValueError(
            f"cannot serialize unregistered state type {name!r}; "
            f"registered: {sorted(STATE_TYPES)} (register_state to extend)")
    path = pathlib.Path(path)
    traced = [f for f, v in zip(state._fields, state)
              if isinstance(v, jax.core.Tracer)]
    if traced:
        raise TypeError(
            f"save_state({name}) materializes every field on the host and "
            f"cannot run under jit/vmap (traced fields: {traced}); "
            "checkpoint from the serving loop, not inside a traced "
            "function")
    payload = {_FIELD + f: np.asarray(v) for f, v in
               zip(state._fields, state)}
    with open(path, "wb") as fh:
        np.savez(fh, __schema__=np.int64(SCHEMA_VERSION),
                 __state__=np.str_(name),
                 __checksums__=_checksum_meta(payload), **payload)
    return path


def load_state(path):
    """Reconstruct the state saved at ``path``; bitwise-identical leaves.
    Truncated/corrupt files (and checksum failures) raise
    ``CheckpointError`` instead of leaking raw zipfile/KeyError tracebacks."""
    with _checkpoint_io(path, "state checkpoint"), \
            np.load(pathlib.Path(path), allow_pickle=False) as z:
        if "__schema__" not in z or "__state__" not in z:
            raise CheckpointError(path, "not a repro state checkpoint")
        schema = int(z["__schema__"])
        if schema not in (1, SCHEMA_VERSION):
            raise CheckpointError(
                path, f"schema v{schema} != supported v{SCHEMA_VERSION}")
        name = str(z["__state__"])
        if name not in STATE_TYPES:
            raise CheckpointError(
                path, f"unknown state type {name!r}; registered: "
                      f"{sorted(STATE_TYPES)}")
        cls = STATE_TYPES[name]
        arrays = {k: z[k] for k in z.files if k.startswith(_FIELD)}
        _verify_checksums(path, z, arrays)
        fields = {k[len(_FIELD):]: jnp.asarray(v) for k, v in arrays.items()}
        if schema == 1 and name in _STATE_V1 \
                and set(fields) != set(cls._fields):
            try:
                fields = _STATE_V1[name](dict(fields))
            except KeyError:
                pass          # not a v1 layout either: reported below
        if set(fields) != set(cls._fields):
            raise CheckpointError(
                path, f"field mismatch for {name}: file has "
                      f"{sorted(fields)}, {name} expects "
                      f"{sorted(cls._fields)} (state schema drifted — "
                      f"migrate the checkpoint)")
        return cls(*(fields[f] for f in cls._fields))


def peek(path) -> dict:
    """Cheap metadata read: {'state': type name, 'schema': int, 'fields':
    {name: (shape, dtype)}} without materializing device arrays."""
    with _checkpoint_io(path, "state checkpoint"), \
            np.load(pathlib.Path(path), allow_pickle=False) as z:
        return {
            "state": str(z["__state__"]),
            "schema": int(z["__schema__"]),
            "fields": {k[len(_FIELD):]: (z[k].shape, str(z[k].dtype))
                       for k in z.files if k.startswith(_FIELD)},
        }


# ---------------------------------------------------------------------------
# Store checkpointing: persist the Sec. 5.2 algebra, not just its output.
# ---------------------------------------------------------------------------

def _kernel_meta(kfn) -> dict:
    """Encode a kernel by value where possible: a ``KernelSpec`` by its
    (frozen, declarative) fields, a registry kernel by name. Anything else
    is opaque — recorded for the error message, re-supplied at load."""
    if isinstance(kfn, cov.KernelSpec):
        return {"kind": "spec", "name": kfn.name, "impl": kfn.impl,
                "fused": kfn.fused, "block_q": kfn.block_q}
    for name, fn in cov.KERNELS.items():
        if fn is kfn:
            return {"kind": "named", "name": name}
    return {"kind": "opaque", "repr": repr(kfn)}


def _kernel_from_meta(meta: dict, override):
    if override is not None:
        return override
    if meta["kind"] == "named":
        return cov.make_kernel(meta["name"])
    if meta["kind"] == "spec":
        return cov.KernelSpec(meta["name"], meta["impl"], meta["fused"],
                              meta["block_q"])
    raise ValueError(
        f"store checkpoint carries an opaque kernel ({meta.get('repr')}); "
        f"pass load_store(..., kfn=<the fit-time kernel>) to restore")


def _spec_meta(spec: api.ServeSpec) -> dict:
    """Encode a ``ServeSpec`` as JSON metadata. Every field but the kernel
    is a plain scalar/tuple; the kernel reuses the kernel encoding above
    (an opaque kernel is recorded and fails loudly at DECODE time, so a
    checkpoint is always writable and re-admission with an explicit
    ``spec=`` override still works)."""
    return {
        "kernel": None if spec.kernel is None else _kernel_meta(spec.kernel),
        "block_q": spec.block_q, "max_batch": spec.max_batch,
        "buckets": None if spec.buckets is None else list(spec.buckets),
        "min_bucket": spec.min_bucket, "routed": spec.routed,
        "alpha": spec.alpha, "max_overflow_groups": spec.max_overflow_groups,
        "cached_cinv": spec.cached_cinv, "dtype": spec.dtype,
    }


def _spec_from_meta(meta: dict) -> api.ServeSpec:
    kernel = meta["kernel"]
    if kernel is not None and kernel["kind"] == "opaque":
        raise ValueError(
            f"store checkpoint's ServeSpec carries an opaque kernel "
            f"({kernel.get('repr')}); the serving policy cannot be "
            f"reconstructed from the artifact alone — pass an explicit "
            f"spec (e.g. TenantRegistry.admit_from_checkpoint(..., "
            f"spec=...))")
    kw = dict(meta, kernel=(None if kernel is None
                            else _kernel_from_meta(kernel, None)))
    buckets = kw["buckets"]
    kw["buckets"] = None if buckets is None else tuple(buckets)
    return api.ServeSpec(**kw)


def _runner_meta(runner) -> dict:
    from repro.parallel.runner import VmapRunner
    if isinstance(runner, VmapRunner):
        a = runner.axis_name
        return {"kind": "vmap", "M": int(runner.M),
                "axis_name": a if isinstance(a, str) else list(a)}
    return {"kind": "opaque", "repr": repr(runner)}


def _runner_from_meta(meta: dict, override):
    from repro.parallel.runner import VmapRunner
    if override is not None:
        return override
    if meta["kind"] == "vmap":
        a = meta["axis_name"]
        return VmapRunner(M=meta["M"],
                          axis_name=a if isinstance(a, str) else tuple(a))
    raise ValueError(
        f"store checkpoint carries an opaque runner ({meta.get('repr')} — "
        f"e.g. a ShardMapRunner, whose mesh is process-local); pass "
        f"load_store(..., runner=<a runner for this process>) to restore")


def _summary_arrays(s) -> dict:
    return {"sum:ydot": s.locals_.ydot, "sum:Sdot": s.locals_.Sdot,
            "sum:G": s.G, "sum:alive": s.alive, "sum:Kss": s.Kss,
            "sum:Kss_L": s.Kss_L, "sum:A_L": s.A_L, "sum:ydd": s.ydd}


def _summary_from(arr) -> "object":
    from repro.core.online import SummaryStore
    from repro.core.ppitc import LocalSummary
    return SummaryStore(LocalSummary(arr["sum:ydot"], arr["sum:Sdot"]),
                        arr["sum:G"], arr["sum:alive"], arr["sum:Kss"],
                        arr["sum:Kss_L"], arr["sum:A_L"], arr["sum:ydd"])


def _pitc_store_arrays(store) -> dict:
    return {"arr:S": store.S, **_summary_arrays(store.store)}


def _pitc_store_from(kfn, params, runner, arr):
    from repro.core.online import PITCStore
    return PITCStore(kfn, params, arr["arr:S"], runner, _summary_from(arr))


_PIC_BLOCK_FIELDS = ("Xb", "yb", "V", "C_L", "Wy")


def _pic_store_arrays(store) -> dict:
    out = {"arr:S": store.S, **_summary_arrays(store.store)}
    out.update({f"blk:{f}": getattr(store.blocks, f)
                for f in _PIC_BLOCK_FIELDS})
    return out


def _pic_store_from(kfn, params, runner, arr):
    from repro.core.online import PICBlocks, PICStore
    blocks = PICBlocks(*(arr[f"blk:{f}"] for f in _PIC_BLOCK_FIELDS))
    return PICStore(kfn, params, arr["arr:S"], runner, _summary_from(arr),
                    blocks)


_PICF_FIELDS = ("Xb", "yb", "F", "Xp", "Lp", "alive", "Phi_L", "yF")


def _picf_store_arrays(store) -> dict:
    return {f"arr:{f}": getattr(store, f) for f in _PICF_FIELDS}


def _picf_store_from(kfn, params, runner, arr):
    from repro.core.picf import PICFStore
    return PICFStore(kfn, params, runner,
                     *(arr[f"arr:{f}"] for f in _PICF_FIELDS))


_SUM_KEYS = ("sum:ydot", "sum:Sdot", "sum:G", "sum:alive", "sum:Kss",
             "sum:Kss_L", "sum:A_L", "sum:ydd")


def _summary_v1(a: dict) -> dict:
    """v1 -> v2 summary: G = Kss_L⁻¹ F, A_L = Kss_L⁻¹ Sdd_L."""
    L = a["sum:Kss_L"]
    a["sum:G"] = jax.vmap(lambda F: _tri(L, F))(a.pop("sum:F"))
    return _whiten_v1(a, "sum:Kss_L", "sum:Sdd_L")


def _pic_store_v1(a: dict) -> dict:
    del a["blk:beta"], a["blk:B"]
    L = a["sum:Kss_L"]
    a["blk:V"] = jax.vmap(lambda K: _tri(L, K))(a.pop("blk:Ksd"))
    return _summary_v1(a)


_STORE_V1 = {"PITCStore": _summary_v1, "PICStore": _pic_store_v1}

# name -> (flatten, rebuild(kfn, params, runner, arrays), expected keys)
STORE_TYPES: dict[str, tuple] = {
    "PITCStore": (_pitc_store_arrays, _pitc_store_from,
                  frozenset(("arr:S",) + _SUM_KEYS)),
    "PICStore": (_pic_store_arrays, _pic_store_from,
                 frozenset(("arr:S",) + _SUM_KEYS
                           + tuple(f"blk:{f}" for f in _PIC_BLOCK_FIELDS))),
    "PICFStore": (_picf_store_arrays, _picf_store_from,
                  frozenset(f"arr:{f}" for f in _PICF_FIELDS)),
}

_PARAM = "param:"


def save_store(path, store, *, spec: api.ServeSpec | None = None
               ) -> pathlib.Path:
    """Write an incremental ``StateStore`` to ``path`` (npz). Arrays —
    summaries, factors, block caches, pivot basis, hyperparameters —
    round-trip bitwise; the kernel and runner are encoded as metadata (see
    module docstring). ``spec=`` additionally embeds the deployment's
    ``ServeSpec`` next to the store, making the checkpoint a COMPLETE
    serving artifact: a restarted fleet member re-admits the tenant —
    posterior, streaming algebra, and serving policy — from this one file
    (``serving.TenantRegistry.admit_from_checkpoint``). Returns the path
    written."""
    name = type(store).__name__
    if name not in STORE_TYPES:
        raise ValueError(
            f"cannot serialize store type {name!r}; "
            f"supported: {sorted(STORE_TYPES)}")
    flatten, _, _ = STORE_TYPES[name]
    leaves = flatten(store)
    traced = [k for k, v in leaves.items()
              if isinstance(v, jax.core.Tracer)]
    if traced:
        raise TypeError(
            f"save_store({name}) materializes every array on the host and "
            f"cannot run under jit/vmap (traced leaves: {traced}); "
            "checkpoint from the serving loop, not inside a traced "
            "function")
    payload = {k: np.asarray(v) for k, v in leaves.items()}
    payload.update({_PARAM + k: np.asarray(v)
                    for k, v in store.params.items()})
    payload["__checksums__"] = _checksum_meta(
        {k: v for k, v in payload.items() if not k.startswith("__")})
    if spec is not None:
        payload["__serve_spec__"] = np.str_(json.dumps(_spec_meta(spec)))
    path = pathlib.Path(path)
    with open(path, "wb") as fh:
        np.savez(fh, __store_schema__=np.int64(STORE_SCHEMA_VERSION),
                 __store__=np.str_(name),
                 __kernel__=np.str_(json.dumps(_kernel_meta(store.kfn))),
                 __runner__=np.str_(json.dumps(_runner_meta(store.runner))),
                 **payload)
    return path


def load_store(path, *, kfn=None, runner=None, with_spec: bool = False):
    """Reconstruct the store saved at ``path``; array members bitwise-
    identical, so a restarted fleet resumes assimilating exactly where the
    checkpoint left off. ``kfn``/``runner`` override the encoded members
    (REQUIRED when the checkpoint recorded them as opaque).

    ``with_spec=True`` returns ``(store, spec)`` where ``spec`` is the
    embedded ``ServeSpec`` (``None`` when the checkpoint predates spec
    embedding or was saved without ``spec=``).

    Truncated/corrupt files — and files whose arrays fail the embedded
    ``__checksums__`` — raise ``CheckpointError`` (path + reason), never a
    raw ``zipfile``/``KeyError`` traceback: the serving revive path must be
    able to tell 'artifact is bad' from 'loader is broken'."""
    with _checkpoint_io(path, "store checkpoint"), \
            np.load(pathlib.Path(path), allow_pickle=False) as z:
        if "__store_schema__" not in z or "__store__" not in z:
            raise CheckpointError(
                path, "not a repro store checkpoint (state checkpoints "
                      "load via load_state)")
        schema = int(z["__store_schema__"])
        if schema not in (1, STORE_SCHEMA_VERSION):
            raise CheckpointError(
                path, f"store schema v{schema} != supported "
                      f"v{STORE_SCHEMA_VERSION}")
        name = str(z["__store__"])
        if name not in STORE_TYPES:
            raise CheckpointError(
                path, f"unknown store type {name!r}; "
                      f"supported: {sorted(STORE_TYPES)}")
        _, rebuild, expect = STORE_TYPES[name]
        raw = {k: z[k] for k in z.files
               if k.startswith(("arr:", "sum:", "blk:", _PARAM))}
        _verify_checksums(path, z, raw)
        arr = {k: jnp.asarray(v) for k, v in raw.items()
               if not k.startswith(_PARAM)}
        if schema == 1 and name in _STORE_V1 and set(arr) != set(expect):
            try:
                arr = _STORE_V1[name](dict(arr))
            except KeyError:
                pass          # not a v1 layout either: reported below
        if set(arr) != set(expect):
            raise CheckpointError(
                path, f"field mismatch for {name}: file has "
                      f"{sorted(arr)}, expected {sorted(expect)} "
                      f"(store schema drifted — migrate the checkpoint)")
        params = {k[len(_PARAM):]: jnp.asarray(v) for k, v in raw.items()
                  if k.startswith(_PARAM)}
        kfn = _kernel_from_meta(json.loads(str(z["__kernel__"])), kfn)
        runner = _runner_from_meta(json.loads(str(z["__runner__"])), runner)
        store = rebuild(kfn, params, runner, arr)
        if not with_spec:
            return store
        spec = (None if "__serve_spec__" not in z.files else
                _spec_from_meta(json.loads(str(z["__serve_spec__"]))))
        return store, spec


def peek_store(path) -> dict:
    """Cheap metadata read for a store checkpoint: type, schema, kernel and
    runner encodings, and array shapes/dtypes."""
    with _checkpoint_io(path, "store checkpoint"), \
            np.load(pathlib.Path(path), allow_pickle=False) as z:
        return {
            "store": str(z["__store__"]),
            "schema": int(z["__store_schema__"]),
            "kernel": json.loads(str(z["__kernel__"])),
            "runner": json.loads(str(z["__runner__"])),
            "serve_spec": (json.loads(str(z["__serve_spec__"]))
                           if "__serve_spec__" in z.files else None),
            "fields": {k: (z[k].shape, str(z[k].dtype)) for k in z.files
                       if k.startswith(("arr:", "sum:", "blk:", _PARAM))},
        }
