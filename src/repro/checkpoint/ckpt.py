"""Checkpointing: pytree ⇄ .npz with slash-joined key paths.

No orbax offline; this is deliberately simple but complete: saves/restores
arbitrary nested dict/tuple/list pytrees of jnp arrays with dtype and
structure preserved, plus atomic write (tmp + rename).  That includes the
compressed runtime's error-feedback carry (DESIGN.md §10): the f32 EF
residual inside the stacked client state, and the codec wire dtypes
(int8/uint8 codes, bf16 scales) round-trip bit-for-bit
(tests/test_checkpoint.py::test_roundtrip_ef_carry).  Whether a stored
state may be RESUMED is the caller's contract: the scan engines put
``uplink_codec`` in the metadata fingerprint and refuse a resume across a
codec change (repro.core.fed_engine / repro.launch.train).
"""
from __future__ import annotations

import json
import os
import tempfile
import zipfile
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _crc_of(items: dict) -> int:
    """Content checksum over key names + raw array bytes, key-sorted so it
    is independent of insertion/zip member order."""
    crc = 0
    for k in sorted(items):
        crc = zlib.crc32(k.encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(items[k]).tobytes(), crc)
    return crc


def _open(path: str):
    """``np.load`` with truncation/bit-rot mapped to a clear ValueError
    (a half-written or corrupted .npz otherwise surfaces as an opaque
    BadZipFile/EOFError deep inside numpy)."""
    try:
        return np.load(path)
    except FileNotFoundError:
        raise
    except (zipfile.BadZipFile, EOFError, OSError, ValueError) as e:
        raise ValueError(f"checkpoint {path!r} is unreadable — truncated "
                         f"or corrupted ({e})") from e


def verify(path: str) -> None:
    """Recompute the stored content checksum; raise ``ValueError`` when the
    file is corrupted (bit rot, doctoring, partial write).  Checkpoints
    written before the checksum existed pass unverified."""
    with _open(path) as data:
        try:
            if "__checksum__" not in data:
                return
            stored = int(data["__checksum__"])
            items = {k: data[k] for k in data.files if k != "__checksum__"}
        except (zlib.error, zipfile.BadZipFile, EOFError, OSError,
                ValueError) as e:
            raise ValueError(f"checkpoint {path!r} is unreadable — "
                             f"truncated or corrupted ({e})") from e
    got = _crc_of(items)
    if got != stored:
        raise ValueError(
            f"checkpoint {path!r} failed its content checksum "
            f"(stored {stored:#010x}, recomputed {got:#010x}) — the file "
            f"was corrupted or modified after it was written")


def _flatten(tree: Any):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = np.asarray(leaf)
    return out, treedef


def save(path: str, tree: Any, metadata: dict | None = None) -> None:
    arrays, _ = _flatten(tree)
    # bf16 has no numpy savez support pre-2.x in some paths; view as uint16
    packed = {}
    dtypes = {}
    for k, v in arrays.items():
        if v.dtype == jnp.bfloat16:
            packed[k] = v.view(np.uint16)
            dtypes[k] = "bfloat16"
        else:
            packed[k] = v
            dtypes[k] = str(v.dtype)
    packed["__dtypes__"] = np.frombuffer(
        json.dumps(dtypes).encode(), np.uint8)
    if metadata:
        packed["__meta__"] = np.frombuffer(
            json.dumps(metadata).encode(), np.uint8)
    packed["__checksum__"] = np.asarray(_crc_of(packed), np.uint32)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    # write through the OPEN tmp file descriptor: np.savez(filename) appends
    # ".npz" to names that lack it, which would strand the mkstemp file and
    # rename a sibling instead — a file object keeps the name exact
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)),
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **packed)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def restore(path: str, like: Any, *, as_numpy: bool = False) -> Any:
    """Restore into the structure of `like` (shapes/dtypes validated).

    Mismatches raise ``KeyError`` / ``ValueError`` with the offending leaf
    path — restoring a checkpoint into the wrong model/run configuration
    must fail loudly, not with a bare assert (or, worse, silently).

    ``as_numpy=True`` keeps the restored leaves as host numpy arrays
    instead of device-putting them — the host-backed client store restores
    a whole population this way, so the device never sees more than the
    active cohort (DESIGN.md §12).

    The file's content checksum (written by :func:`save`) is verified
    first — a truncated or bit-rotted checkpoint fails loudly here rather
    than resuming a silently-wrong run.
    """
    verify(path)
    with _open(path) as data:
        dtypes = json.loads(bytes(data["__dtypes__"]).decode())
        flat_like, treedef = jax.tree.flatten_with_path(like)
        leaves = []
        for pth, leaf in flat_like:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in pth)
            if key not in data:
                stored = sorted(k for k in data.files
                                if not k.startswith("__"))
                raise KeyError(
                    f"checkpoint {path!r} has no leaf {key!r}; it stores "
                    f"{stored[:8]}{'…' if len(stored) > 8 else ''} — the "
                    f"restore target has a different tree structure")
            arr = data[key]
            if dtypes[key] == "bfloat16":
                arr = arr.view(jnp.bfloat16)
            # shape/dtype come from attribute access so `like` may hold
            # numpy or jax arrays (or ShapeDtypeStructs) without forcing a
            # device transfer of the template itself
            want_shape = tuple(np.shape(leaf))
            want_dtype = np.dtype(getattr(leaf, "dtype",
                                          np.asarray(leaf).dtype))
            if arr.shape != want_shape:
                raise ValueError(
                    f"checkpoint leaf {key!r} has shape {arr.shape} but the "
                    f"restore target expects {want_shape} — the checkpoint "
                    f"was written for a different model/run configuration")
            if as_numpy:
                leaves.append(np.asarray(arr).astype(want_dtype, copy=False))
            else:
                leaves.append(jnp.asarray(arr, want_dtype))
        return jax.tree.unflatten(treedef, leaves)


def load_subtree(path: str, prefix: str) -> Any:
    """Load the stored subtree under slash-joined ``prefix`` as a nested
    dict of host numpy arrays, WITHOUT a template.

    :func:`restore` validates against a ``like`` tree, which requires the
    caller to already know every leaf's shape — impossible for state whose
    extent is data-dependent, e.g. the async engine's in-flight record
    table (``n_pending`` varies with where the run was killed, DESIGN.md
    §13).  Nested structure is rebuilt from the key paths; keys come back
    as strings (list/tuple indices included).  Returns ``{}`` when nothing
    is stored under the prefix."""
    out: dict = {}
    pre = prefix.rstrip("/") + "/"
    with _open(path) as data:
        dtypes = json.loads(bytes(data["__dtypes__"]).decode())
        for key in data.files:
            if key.startswith("__") or not key.startswith(pre):
                continue
            arr = data[key]
            if dtypes[key] == "bfloat16":
                arr = arr.view(jnp.bfloat16)
            node = out
            parts = key[len(pre):].split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = np.asarray(arr)
    return out


def metadata(path: str) -> dict:
    with _open(path) as data:
        if "__meta__" in data:
            return json.loads(bytes(data["__meta__"]).decode())
    return {}


def check_fingerprint(path: str, meta: dict, want: dict, *,
                      defaults: dict | None = None,
                      ignore: tuple = ()) -> None:
    """Refuse resuming across a run-configuration change.

    ``meta`` is the checkpoint's stored metadata (mutated in place:
    ``defaults`` are backfilled for fingerprint fields older checkpoints
    did not record — e.g. ``uplink_codec`` pre-§10, ``client_store``
    pre-§12 — so old checkpoints keep resuming under the default they were
    written with).  ``want`` is the current run's fingerprint; any field
    not in ``ignore`` that differs raises ``ValueError`` naming the
    mismatched fields.
    """
    for k, v in (defaults or {}).items():
        meta.setdefault(k, v)
    stale = {k: (meta.get(k), v) for k, v in want.items()
             if k not in ignore and meta.get(k) != v}
    if stale:
        raise ValueError(
            f"checkpoint {path!r} was written by a different run "
            f"configuration; refusing to resume (mismatched fields: "
            f"{ {k: f'{a!r} != {b!r}' for k, (a, b) in stale.items()} })")
