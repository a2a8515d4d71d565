"""Fingerprinted on-disk store of serialized ladder executables.

``utils/cache.py`` extends jax's persistent compile cache across
processes on one machine; this module is the next rung: a *portable,
inspectable* store of the serving ladder's compiled executables, keyed
explicitly so a restarted (or freshly scaled-up) replica can load its
whole ``(B, T)`` rung ladder before admission instead of re-paying jit
compilation per rung (``serving/warmstore.py`` is the runtime plane on
top; ``tools/aot_infer.py --emit-store`` populates it offline).

Key schema — one entry per
``(preset, tier, model version, rung (B, T))`` under a *fingerprint*
directory::

    <root>/<fp-hash>/<preset>--<tier>--<version>--b{B}xt{T}.wse
    <root>/<fp-hash>/FINGERPRINT          # the full fingerprint string

The fingerprint carries jax/jaxlib/libtpu versions plus the platform
jax runs on (and, for host-locked formats, the machine type): CPU AOT
artifacts loaded on a host with different machine features abort the
process, so that class turns into a counted, non-fatal *reject* here
instead of a crash, because a mismatched entry lives in a different
directory and is never deserialized.

Entry file format: one JSON meta line, ``\\n``, then the payload::

    {"format": "xc"|"hlo", "preset": ..., "tier": ..., "version": ...,
     "batch": B, "frames": T, "fingerprint": ..., "sig": ...}

- ``"xc"`` — ``jax.experimental.serialize_executable`` payload
  (pickled ``(payload, in_tree, out_tree)``): a *loaded-executable*
  round trip, zero XLA work at deserialize. Machine-locked — exactly
  what the fingerprint guards.
- ``"hlo"`` — ``jax.export`` StableHLO bytes: portable across hosts of
  one platform; deserialize is cheap but the first call per shape still
  compiles (no retrace). The offline AOT tools emit this when the
  loaded-executable form can't travel.

``sig`` is a hash of the argument pytree structure + leaf
shapes/dtypes (:func:`tree_signature`): a checkpoint that changed
shape under an unchanged version label is rejected, not crashed into.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import re
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

ENTRY_SUFFIX = ".wse"
FORMAT_EXECUTABLE = "xc"
FORMAT_EXPORTED = "hlo"

_SAFE = re.compile(r"[^A-Za-z0-9_.-]+")


def _safe(part: str) -> str:
    """Filename-safe key component ('' -> 'none': the key positions
    are structural, an empty component would make names unparseable)."""
    part = _SAFE.sub("_", str(part))
    return part or "none"


def _versions() -> Dict[str, str]:
    out = {}
    try:
        import jax

        out["jax"] = jax.__version__
    except Exception:
        out["jax"] = "unknown"
    try:
        import jaxlib

        out["jaxlib"] = jaxlib.__version__
    except Exception:
        out["jaxlib"] = "unknown"
    libtpu = "none"
    try:
        from importlib import metadata

        for dist in ("libtpu", "libtpu-nightly"):
            try:
                libtpu = metadata.version(dist)
                break
            except metadata.PackageNotFoundError:
                continue
    except Exception:
        pass
    out["libtpu"] = libtpu
    return out


def host_fingerprint() -> str:
    """Fingerprint for host-locked (``"xc"``) entries: jax/jaxlib/
    libtpu versions, the platform jax runs on, and the machine type
    (the CPU-feature axis behind the abort class in the module
    docstring)."""
    import platform

    import jax

    return (fingerprint_for(jax.default_backend())
            + f"|machine={platform.machine()}")


def fingerprint_for(platform_name: str) -> str:
    """Portable fingerprint for a *target* platform (offline AOT
    emitters compiling for a host they are not on): versions + the
    platform name, no machine axis — the ``"hlo"`` format recompiles
    at load, and a TPU executable's host code is not CPU-feature
    bound the way CPU AOT artifacts are."""
    v = _versions()
    return ("jax={jax}|jaxlib={jaxlib}|libtpu={libtpu}".format(**v)
            + f"|plat={platform_name}")


def _fp_hash(fp: str) -> str:
    return hashlib.sha256(fp.encode()).hexdigest()[:16]


def tree_signature(tree) -> str:
    """Structure + leaf shapes/dtypes hash of an argument pytree —
    cheap (no device reads) and exactly the compatibility an
    executable's calling convention requires."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def _desc(x):
        dt = getattr(x, "dtype", None)
        if dt is None:
            dt = np.asarray(x).dtype
        return f"{tuple(np.shape(x))}:{np.dtype(dt).name}"

    blob = str(treedef) + ";" + ",".join(_desc(l) for l in leaves)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


@dataclass(frozen=True)
class StoreKey:
    """One ladder executable's identity (the fingerprint is the
    directory, not part of the key)."""

    preset: str
    tier: str
    version: str
    batch: int
    frames: int

    @property
    def rung(self) -> str:
        return f"{self.batch}x{self.frames}"

    def filename(self) -> str:
        return (f"{_safe(self.preset)}--{_safe(self.tier)}--"
                f"{_safe(self.version)}--b{int(self.batch)}x"
                f"t{int(self.frames)}{ENTRY_SUFFIX}")


_FNAME = re.compile(
    r"^(?P<preset>[^-]+(?:-[^-]+)*?)--(?P<tier>[^-]+(?:-[^-]+)*?)--"
    r"(?P<version>[^-]+(?:-[^-]+)*?)--b(?P<batch>\d+)xt(?P<frames>\d+)"
    + re.escape(ENTRY_SUFFIX) + "$")


def parse_filename(name: str) -> Optional[StoreKey]:
    m = _FNAME.match(name)
    if not m:
        return None
    return StoreKey(m.group("preset"), m.group("tier"),
                    m.group("version"), int(m.group("batch")),
                    int(m.group("frames")))


class AotStore:
    """Directory-backed executable store (see module docstring).

    All methods are best-effort and exception-free by contract where
    the serving path calls them (``lookup``/``rungs``): a corrupt or
    half-written entry is a miss, never a crash — restarts must not be
    hostage to the store.
    """

    def __init__(self, root: str, fingerprint: Optional[str] = None,
                 fallback_fingerprints: Tuple[str, ...] = ()):
        self.root = str(root)
        self.fingerprint = fingerprint or host_fingerprint()
        self.fp_dir = os.path.join(self.root, _fp_hash(self.fingerprint))
        # Additional fingerprints a lookup treats as hits — the
        # runtime registers its platform's PORTABLE fingerprint here
        # (fingerprint_for) so entries the offline AOT tools emitted
        # for this platform preload instead of rejecting. Writes only
        # ever land under the primary fingerprint.
        self.fallback_dirs = [
            os.path.join(self.root, _fp_hash(fp))
            for fp in fallback_fingerprints
            if fp and fp != self.fingerprint]

    # -- writing ---------------------------------------------------------
    def put(self, key: StoreKey, payload: bytes, fmt: str,
            sig: str = "", **meta_extra) -> str:
        """Atomically write one entry; returns its path. The meta line
        restates the key and the full fingerprint so an entry is
        self-describing even when moved between roots."""
        if fmt not in (FORMAT_EXECUTABLE, FORMAT_EXPORTED):
            raise ValueError(f"unknown store format {fmt!r}")
        os.makedirs(self.fp_dir, exist_ok=True)
        marker = os.path.join(self.fp_dir, "FINGERPRINT")
        if not os.path.exists(marker):
            with open(marker, "w") as fh:
                fh.write(self.fingerprint + "\n")
        meta = {"format": fmt, "preset": key.preset, "tier": key.tier,
                "version": key.version, "batch": int(key.batch),
                "frames": int(key.frames),
                "fingerprint": self.fingerprint, "sig": sig,
                "created": round(time.time(), 3), **meta_extra}
        path = os.path.join(self.fp_dir, key.filename())
        fd, tmp = tempfile.mkstemp(dir=self.fp_dir,
                                   suffix=ENTRY_SUFFIX + ".tmp")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(json.dumps(meta).encode() + b"\n")
                fh.write(payload)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    # -- reading ---------------------------------------------------------
    @staticmethod
    def _read_entry(path: str) -> Optional[Tuple[dict, bytes]]:
        try:
            with open(path, "rb") as fh:
                header = fh.readline()
                meta = json.loads(header.decode())
                if not isinstance(meta, dict):
                    return None
                return meta, fh.read()
        except (OSError, ValueError, UnicodeDecodeError):
            return None

    def get(self, key: StoreKey) -> Optional[Tuple[dict, bytes]]:
        """(meta, payload) for ``key`` under THIS fingerprint, or
        None."""
        return self._read_entry(os.path.join(self.fp_dir,
                                             key.filename()))

    def lookup(self, key: StoreKey
               ) -> Tuple[str, Optional[dict], Optional[bytes]]:
        """('hit', meta, payload) | ('reject', meta, None) |
        ('miss', None, None).

        A *reject* means the entry exists under a DIFFERENT fingerprint
        only — the machine/toolchain the executable was built for is
        not this one (a foreign-host artifact would abort): the caller
        falls back to jit and counts it, and the foreign payload is
        never deserialized."""
        got = self.get(key)
        if got is not None:
            return "hit", got[0], got[1]
        for d in self.fallback_dirs:
            entry = self._read_entry(os.path.join(d, key.filename()))
            if entry is not None:
                return "hit", entry[0], entry[1]
        try:
            subdirs = (os.listdir(self.root)
                       if os.path.isdir(self.root) else [])
        except OSError:
            subdirs = []
        for sub in subdirs:
            d = os.path.join(self.root, sub)
            if (d == self.fp_dir or d in self.fallback_dirs
                    or not os.path.isdir(d)):
                continue
            p = os.path.join(d, key.filename())
            if os.path.exists(p):
                entry = self._read_entry(p)
                return "reject", entry[0] if entry else None, None
        return "miss", None, None

    def keys(self) -> List[StoreKey]:
        """Every parseable entry under this fingerprint."""
        try:
            names = sorted(os.listdir(self.fp_dir))
        except OSError:
            return []
        out = []
        for name in names:
            key = parse_filename(name)
            if key is not None:
                out.append(key)
        return out

    def rungs(self, preset: str, tier: str, version: str
              ) -> List[Tuple[int, int]]:
        """Stored ``(B, T)`` rungs for one (preset, tier, version)."""
        return sorted((k.batch, k.frames) for k in self.keys()
                      if (k.preset, k.tier, k.version)
                      == (_safe(preset), _safe(tier), _safe(version)))


# -- serialization codecs (lazy jax imports: importable store-side) ------

def serialize_compiled(compiled) -> bytes:
    """``"xc"``: pickle a loaded executable's serialized form — the
    true zero-compile round trip (deserialize loads, never compiles).
    The ids of the devices it was compiled for travel with it: jax's
    own payload does not say, and a load that guesses "every device of
    the backend" builds a callable that wants one shard per device.
    They are read off the unloaded executable that ``se.serialize``
    pickles, so an executable compiled for a described topology
    (``tools/aot_*.py --emit-store``) says them too. No fallback where
    jax moves that attribute: the export raises, and every caller
    treats an export as best-effort."""
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    device_ids = [int(d.id) for d in
                  compiled._executable._unloaded_executable.device_list]
    return pickle.dumps((payload, in_tree, out_tree, device_ids))


def deserialize_compiled(blob: bytes):
    """Inverse of :func:`serialize_compiled`: a callable with the
    original function's signature, backed by the stored executable and
    bound to the devices it was compiled for. Raises on a blob without
    device ids (written before they were stored) or with an id this
    backend does not have: ``WarmStore.preload_replica`` rejects the
    entry and the rung compiles through jit."""
    import jax
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def serialize_exported(exported) -> bytes:
    """``"hlo"``: a ``jax.export.Exported``'s portable bytes."""
    return bytes(exported.serialize())


def deserialize_exported(blob: bytes):
    """Callable over a stored ``"hlo"`` entry (compiles at first call
    per shape — cheap next to a retrace, but not zero)."""
    import jax.export as jexport

    return jexport.deserialize(bytearray(blob)).call


def deserialize_entry(meta: dict, payload: bytes):
    """Format-dispatched deserialize -> callable."""
    fmt = meta.get("format")
    if fmt == FORMAT_EXECUTABLE:
        return deserialize_compiled(payload)
    if fmt == FORMAT_EXPORTED:
        return deserialize_exported(payload)
    raise ValueError(f"unknown store format {fmt!r}")
