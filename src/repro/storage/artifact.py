"""On-disk NestQuant artifacts (DESIGN.md Sec. 10).

The paper's deployment claim is that you ship and store ONE NestQuant
model and switch operating points by paging lower-bit weights in and
out.  An artifact is that executable claim: a directory holding

* ``manifest.json`` - format version, the ladder, per-leaf metadata
  (pytree path, logical shape, bits, block), the :class:`~repro.core.
  recipe.QuantRecipe` that produced the tree, and per-segment byte
  sizes + SHA-256 checksums plus per-array offsets + CRC-32s;
* ``base.seg`` - every leaf's packed base words, the FP32 scales, and
  the dense (non-nested) leaves: everything rung 0 needs;
* ``delta_<k>.seg`` - every leaf's packed level-k delta stream: exactly
  what the rung k -> k+1 upgrade pages in.

Arrays are written as raw little-endian bytes straight from the packed
words - an artifact round-trips bit-exactly with ZERO densification in
either direction.  A cold boot reads only ``manifest.json`` +
``base.seg``; delta segments are fetched on demand by a
:class:`~repro.storage.pager.FilePager` (possibly arriving later - see
progressive delivery in serving.engine).

Tree structure is recorded per leaf as a list of dict keys / sequence
indices, so artifacts cover the dict/list/tuple parameter trees the
models here produce (tuples restore as lists; custom container nodes
are rejected at save time with a clear error).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..core.nesting import NestedTensor

MANIFEST = "manifest.json"
FORMAT = "nestquant-artifact"
VERSION = 1


class ArtifactError(RuntimeError):
    """Malformed, corrupted, or not-yet-delivered artifact content."""


def _np(arr) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(jax.device_get(arr)))


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes                      # jax dependency: bf16 et al.
        return np.dtype(getattr(ml_dtypes, name))


def _path_elems(path) -> List[dict]:
    """JSON-able pytree path: [{'k': key} | {'i': index}, ...]."""
    elems = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            elems.append({"k": str(e.key)})
        elif isinstance(e, jax.tree_util.SequenceKey):
            elems.append({"i": int(e.idx)})
        else:
            raise ArtifactError(
                f"unsupported pytree node key {e!r} in {jax.tree_util.keystr(path)}; "
                "artifacts support dict/list/tuple parameter trees")
    return elems


def _assign(root, elems: List[dict], value):
    cur = root
    for j, e in enumerate(elems):
        last = j == len(elems) - 1
        make = (lambda: {} if "k" in elems[j + 1] else []) if not last else None
        if "k" in e:
            key = e["k"]
            if last:
                cur[key] = value
            else:
                if key not in cur:
                    cur[key] = make()
                cur = cur[key]
        else:
            i = e["i"]
            while len(cur) <= i:
                cur.append(None)
            if last:
                cur[i] = value
            else:
                if cur[i] is None:
                    cur[i] = make()
                cur = cur[i]


def _build_tree(items: List[Tuple[List[dict], Any]]):
    if len(items) == 1 and not items[0][0]:
        return items[0][1]                    # a bare single-leaf artifact
    root: Any = {} if "k" in items[0][0][0] else []
    for elems, value in items:
        _assign(root, elems, value)
    return root


class _SegmentWriter:
    """Streams arrays into one segment file, accumulating the SHA-256
    and recording per-array (offset, nbytes, dtype, shape, crc32)."""

    def __init__(self, dirpath: str, name: str):
        self.name = name
        self.file = f"{name}.seg"
        self._f = open(os.path.join(dirpath, self.file), "wb")
        self._sha = hashlib.sha256()
        self.nbytes = 0

    def put(self, arr) -> dict:
        host = _np(arr)                       # ONE device_get per array
        raw = host.tobytes()
        spec = {"segment": self.name, "offset": self.nbytes,
                "nbytes": len(raw), "dtype": str(host.dtype),
                "shape": [int(d) for d in host.shape],
                "crc32": zlib.crc32(raw)}
        self._f.write(raw)
        self._sha.update(raw)
        self.nbytes += len(raw)
        return spec

    def close(self) -> dict:
        self._f.close()
        return {"file": self.file, "nbytes": self.nbytes,
                "sha256": self._sha.hexdigest()}


def save_artifact(nested_params, path: str, recipe=None) -> dict:
    """Serialize a quantized tree (+ its recipe) to an artifact directory.

    Every leaf must be fully resident (no paged-out delta streams) - save
    from the tree that ``quantize`` returned, not from a live store's
    stripped residency.  Written atomically (temp dir + ``os.replace``).
    Returns the manifest dict."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
    depth = 1
    for _, leaf in flat:
        if isinstance(leaf, NestedTensor):
            depth = max(depth, leaf.num_rungs)

    parent = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=parent, prefix=".tmp_artifact_")
    try:
        base = _SegmentWriter(tmp, "base")
        deltas = [_SegmentWriter(tmp, f"delta_{i}") for i in range(depth - 1)]
        leaves = []
        for p, leaf in flat:
            entry: Dict[str, Any] = {"path": jax.tree_util.keystr(p),
                                     "elems": _path_elems(p)}
            if isinstance(leaf, NestedTensor):
                if leaf.resident_levels != len(leaf.deltas):
                    raise ArtifactError(
                        f"{entry['path']}: delta streams are paged out; "
                        "save_artifact needs the fully resident tree")
                entry.update(
                    kind="nested", shape=list(leaf.shape),
                    bits=list(leaf.bits), block=int(leaf.block),
                    arrays={"base": base.put(leaf.w_base),
                            "scale": base.put(leaf.scale),
                            "deltas": [deltas[i].put(d)
                                       for i, d in enumerate(leaf.deltas)]})
            else:
                entry.update(kind="dense",
                             arrays={"value": base.put(leaf)})
            leaves.append(entry)
        manifest = {
            "format": FORMAT, "version": VERSION,
            "num_delta_levels": depth - 1,
            "recipe": (json.loads(recipe.to_json())
                       if recipe is not None else None),
            "segments": {w.name: w.close() for w in [base] + deltas},
            "leaves": leaves,
        }
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=1)
        final = os.path.abspath(path)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return manifest


class Artifact:
    """An opened artifact: manifest in memory, segments on disk.

    Tracks how many bytes were actually read per segment
    (:attr:`bytes_read`, :attr:`segments_read`) so deployments - and the
    cold-boot tests - can assert what really went over the wire."""

    def __init__(self, path: str):
        self.dir = os.path.abspath(path)
        mpath = os.path.join(self.dir, MANIFEST)
        if not os.path.exists(mpath):
            raise FileNotFoundError(f"no {MANIFEST} in {self.dir}")
        with open(mpath) as f:
            self.manifest = json.load(f)
        if self.manifest.get("format") != FORMAT:
            raise ArtifactError(f"{mpath} is not a {FORMAT}")
        self._by_path = {l["path"]: l for l in self.manifest["leaves"]}
        self.bytes_read: Dict[str, int] = {
            "manifest": os.path.getsize(mpath)}
        self.segments_read: set = set()

    # -- manifest-level views ------------------------------------------
    @property
    def num_delta_levels(self) -> int:
        return int(self.manifest["num_delta_levels"])

    @property
    def recipe_dict(self) -> Optional[dict]:
        return self.manifest.get("recipe")

    def leaf(self, path: str) -> dict:
        try:
            return self._by_path[path]
        except KeyError:
            raise KeyError(f"artifact has no leaf {path!r}") from None

    def delta_segment(self, level: int) -> str:
        return f"delta_{level}"

    def segment_nbytes(self, name: str) -> int:
        return int(self.manifest["segments"][name]["nbytes"])

    def total_nbytes(self) -> int:
        """Manifest + every segment: the full artifact on the wire."""
        return (self.bytes_read["manifest"]
                + sum(int(s["nbytes"])
                      for s in self.manifest["segments"].values()))

    def segment_path(self, name: str) -> str:
        return os.path.join(self.dir, self.manifest["segments"][name]["file"])

    def segment_available(self, name: str) -> bool:
        """Segment file present on disk (progressive delivery: delta
        segments may arrive after the base)."""
        return os.path.exists(self.segment_path(name))

    # -- byte-level reads ----------------------------------------------
    def _count(self, name: str, n: int):
        self.bytes_read[name] = self.bytes_read.get(name, 0) + n
        self.segments_read.add(name)

    def read_segment(self, name: str) -> bytes:
        """Read one whole segment, verified against its SHA-256."""
        if not self.segment_available(name):
            raise ArtifactError(f"segment {name!r} not delivered yet "
                                f"({self.segment_path(name)} missing)")
        with open(self.segment_path(name), "rb") as f:
            raw = f.read()
        meta = self.manifest["segments"][name]
        if len(raw) != meta["nbytes"]:
            raise ArtifactError(f"segment {name!r}: {len(raw)} bytes on "
                                f"disk, manifest says {meta['nbytes']}")
        if hashlib.sha256(raw).hexdigest() != meta["sha256"]:
            raise ArtifactError(f"segment {name!r}: SHA-256 mismatch "
                                "(corrupted artifact)")
        self._count(name, len(raw))
        return raw

    def read_array(self, spec: dict, verify: bool = True,
                   buf: Optional[bytes] = None) -> np.ndarray:
        """Read one array - from ``buf`` if the caller already holds the
        whole segment, else just that byte range of the segment file."""
        if buf is not None:
            raw = buf[spec["offset"]:spec["offset"] + spec["nbytes"]]
        else:
            if not self.segment_available(spec["segment"]):
                raise ArtifactError(
                    f"segment {spec['segment']!r} not delivered yet")
            with obs.span("page_in.read", nbytes=spec["nbytes"]), \
                    open(self.segment_path(spec["segment"]), "rb") as f:
                f.seek(spec["offset"])
                raw = f.read(spec["nbytes"])
            self._count(spec["segment"], len(raw))
        if len(raw) != spec["nbytes"]:
            raise ArtifactError(f"short read in {spec['segment']!r} at "
                                f"offset {spec['offset']}")
        if verify:
            with obs.span("page_in.crc", nbytes=len(raw)):
                observed = zlib.crc32(raw)
            if observed != spec["crc32"]:
                from .pager import CorruptStreamError   # lazy: no cycle
                raise CorruptStreamError(
                    f"CRC-32 mismatch in {spec['segment']!r} at offset "
                    f"{spec['offset']}: expected {spec['crc32']:#010x}, "
                    f"observed {observed:#010x} (corrupted artifact)")
        return np.frombuffer(raw, dtype=_resolve_dtype(spec["dtype"])) \
                 .reshape(spec["shape"])

    def verify(self):
        """Check every delivered segment against its SHA-256."""
        for name in self.manifest["segments"]:
            if self.segment_available(name):
                self.read_segment(name)

    # -- boot ----------------------------------------------------------
    def load_base_tree(self):
        """Reconstruct the nested pytree from manifest + base segment ONLY.

        Nested leaves come back at rung 0 with every delta slot ``None``
        (non-resident; a pager supplies them on upgrade); dense leaves
        come back in full.  Reads nothing but ``base.seg``."""
        buf = self.read_segment("base")
        items = []
        for entry in self.manifest["leaves"]:
            a = entry["arrays"]
            if entry["kind"] == "nested":
                leaf = NestedTensor(
                    w_base=jnp.asarray(self.read_array(a["base"], buf=buf)),
                    deltas=(None,) * len(a["deltas"]),
                    scale=jnp.asarray(self.read_array(a["scale"], buf=buf)),
                    shape=tuple(entry["shape"]),
                    bits=tuple(entry["bits"]),
                    block=int(entry["block"]),
                    rung=0)
            else:
                leaf = jnp.asarray(self.read_array(a["value"], buf=buf))
            items.append((entry["elems"], leaf))
        return _build_tree(items)

    def recipe(self):
        """The saved QuantRecipe (default predicate), or None."""
        if self.recipe_dict is None:
            return None
        from ..core.recipe import QuantRecipe
        return QuantRecipe.from_json(json.dumps(self.recipe_dict))


def open_artifact(path: str) -> Artifact:
    """Open an artifact directory, reading ONLY the manifest."""
    return Artifact(path)


def load_store(path: str, mode="part", pager=None, verify: bool = True,
               **store_kwargs):
    """Cold-boot a :class:`~repro.core.switching.NestQuantStore` from an
    artifact: manifest + base segment are read now, delta streams page in
    through a :class:`~repro.storage.pager.FilePager` on demand."""
    from ..core.switching import NestQuantStore
    from .pager import FilePager
    art = path if isinstance(path, Artifact) else open_artifact(path)
    tree = art.load_base_tree()
    if pager is None:
        pager = FilePager(art, verify=verify)
    return NestQuantStore(tree, mode=mode, pager=pager, **store_kwargs)
