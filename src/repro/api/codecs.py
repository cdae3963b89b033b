"""Versioned binary wire codec for compressed parameter trees (DESIGN.md §7).

A *payload* is the serialized form of a storage pytree (the thing
``compress_tree`` / ``compress_params`` produce): ``CompressedVariable``
leaves travel as their exact-width packed bitstream (11 bits/param for
S1E3M7 — the paper's communication saving), everything else travels raw.
The codec frames the payload on the host and packs codes on the device; it
is bit-exact: ``decode(encode(t)) == t`` code-for-code, so wire transport
composes with the storage-mode numerics without introducing a second
rounding step.

Frame layout (little-endian, version 1)::

    magic     4s   b"OMCW"
    version   u16
    flags     u16  bit 0: payload is a delta against a base tree
    round     u32  producer round index (informational)
    mlen      u32  manifest length in bytes
    blen      u64  body length in bytes
    crc       u32  zlib.crc32(manifest + body)
    digest    u32  tree_digest of the delta base (0 for full payloads);
                   decode verifies the receiver's base tree against it, so
                   applying a delta to the wrong round's model fails loudly
    manifest  mlen bytes of JSON (tagged leaf paths — dict/list/tuple
              containers are preserved — kinds, shapes, modes)
    body      blen bytes (per-leaf sections in manifest order)

Per-leaf body sections:

  * ``omc``/``full``:  s (f32), b (f32), packed codes (u32 words).
  * ``omc``/``delta``: s, b, sorted u32 indices of changed codes, packed
    XOR-of-codes for those indices.  The XOR is against the *base* tree's
    codes (round r-1 for a repeat download); after a small server step most
    codes are unchanged, so the sparse form shrinks repeat downloads.
  * ``raw``/``full``:  the array bytes.
  * ``raw``/``delta``: sorted u32 indices + u32 XOR words over the array's
    32-bit bitview (4-byte dtypes only).

The encoder picks ``delta`` per leaf only when it is actually smaller than
``full`` (a dense update degenerates to full — no silent size regression),
so ``encode_payload(tree, base=prev)`` is never worse than
``encode_payload(tree)`` by more than the per-leaf mode flag.

Strategy leaves (DESIGN.md §11): the ``omc`` and ``raw`` kinds above are
built in; the compression-strategy zoo (:mod:`repro.compress`) registers
additional leaf kinds (``topk``, ``ternary``, ``pipeline``) through
:func:`register_leaf_codec`, and payloads carrying them are stamped with a
*strategy tag* + per-strategy wire version in the manifest.  ``decode``
verifies the tag against the registered zoo — an unknown strategy or a
version mismatch is a loud :class:`CodecError`, never silent corruption.
The sparse XOR-delta above is the OMC strategy's delta rule; registered
kinds travel full-only unless their codec implements its own delta.

Byte accounting: for a full payload the body is exactly
``packed_bytes(n, fmt) + 8·s.size`` per compressed leaf plus ``itemsize·n``
per raw leaf — the same accounting ``tree_bytes_report`` /
``state_bytes_report`` call ``packed_bytes`` — so wire measurements and the
paper-table byte columns reconcile by construction
(:func:`payload_bytes_report` computes it without serializing).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import struct
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.formats import FloatFormat, uint_container
from repro.core.store import CompressedVariable, is_compressed
from repro.obs import null_span

MAGIC = b"OMCW"
WIRE_VERSION = 1
SUPPORTED_VERSIONS = (1,)

FLAG_DELTA = 1 << 0

# magic, version, flags, round, manifest len, body len, crc, base digest
_HEADER = struct.Struct("<4sHHIIQII")
_PVT_BYTES_PER_ENTRY = 8  # s and b, f32 each
# Fields per device call of the wire pack/unpack.  A multiple of 32, so each
# chunk ends on a word boundary and the chunks' words concatenate into the
# canonical stream.  It bounds what one call holds on the device whatever
# the size of the leaf, and the TPU compile time of each call's shape
# (which grows with the call's size: 2 s at 2^18 fields, 45 s at 2^22).
_CHUNK_FIELDS = 1 << 18

# Host spans (DESIGN.md §15), ``omc.<name>`` in a profiler trace: one
# ``codec.encode`` / ``codec.decode`` a payload; ``codec.d2h`` around every
# read of a device array to the host; ``codec.h2d`` around every upload;
# ``codec.pack`` / ``codec.unpack`` around each chunk's kernel call.  A full
# ``omc`` leaf crosses once each way: its codes are cut into chunks, packed
# and joined on the device and its words read once (with s and b: three
# reads); decode uploads the words once (with s and b: three uploads) and
# unpacks and joins them on the device, reading nothing back.


def _to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``; a device array's read is a ``codec.d2h``
    span."""
    if isinstance(x, jax.Array):
        with null_span(None, "codec.d2h"):
            return np.asarray(x, dtype)
    return np.asarray(x, dtype)


def _to_device(x, dtype=None) -> jax.Array:
    with null_span(None, "codec.h2d"):
        return jnp.asarray(x, dtype)


class CodecError(ValueError):
    """Malformed, corrupt, or version-incompatible payload."""


# ---------------------------------------------------------------------------
# strategy leaf-codec registry (DESIGN.md §11).  repro.compress registers the
# zoo's kinds at import; decode lazily imports it on first contact with a
# strategy payload so a fresh process can always decode.
# ---------------------------------------------------------------------------

_LEAF_CODECS: Dict[str, Tuple[type, Any, Any]] = {}


def register_leaf_codec(kind: str, leaf_type: type, encode_fn, decode_fn) -> None:
    """Register a strategy leaf kind: ``encode_fn(leaf, base) -> (meta,
    [chunks])`` and ``decode_fn(meta, body, off, base) -> (leaf, off)``.
    The body section MUST measure exactly ``leaf.wire_body_bytes()`` bytes
    so every ledger reconciles (§11 byte-accounting obligation)."""
    if kind in ("omc", "raw"):
        raise ValueError(f"leaf kind {kind!r} is built in")
    prev = _LEAF_CODECS.get(kind)
    if prev is not None and prev[0] is not leaf_type:
        raise ValueError(f"leaf kind {kind!r} already registered")
    _LEAF_CODECS[kind] = (leaf_type, encode_fn, decode_fn)


def _ensure_strategy_codecs() -> None:
    """Import the zoo (idempotent) so its leaf codecs are registered."""
    import repro.compress  # noqa: F401  (registration happens at import)


def _leaf_kind(leaf) -> Optional[str]:
    for kind, (leaf_type, _, _) in _LEAF_CODECS.items():
        if isinstance(leaf, leaf_type):
            return kind
    return None


def _check_strategy_tag(manifest: Dict[str, Any]) -> None:
    """Reject unknown strategy tags / wire-version mismatches (CodecError)."""
    name = manifest.get("strategy")
    if name is None:
        return
    _ensure_strategy_codecs()
    from repro.compress import available_strategies, strategy_class

    try:
        cls = strategy_class(name)
    except KeyError:
        raise CodecError(
            f"unknown compression strategy tag {name!r}; "
            f"registered zoo: {available_strategies()}"
        ) from None
    sver = int(manifest.get("strategy_version", 0))
    if sver != cls.wire_version:
        raise CodecError(
            f"strategy {name!r} wire version mismatch: payload carries "
            f"v{sver}, this zoo speaks v{cls.wire_version}"
        )


@dataclasses.dataclass(frozen=True)
class PayloadInfo:
    """Parsed frame metadata (available without decoding the body)."""

    version: int
    flags: int
    round_index: int
    header_bytes: int  # fixed header + manifest
    body_bytes: int
    total_bytes: int
    num_leaves: int
    num_compressed: int
    num_delta: int
    base_digest: int  # tree_digest of the delta base; 0 for full payloads
    strategy: Optional[str] = None  # zoo strategy tag (None: plain OMC frame)
    strategy_version: int = 0  # per-strategy wire version (0: untagged)

    @property
    def is_delta(self) -> bool:
        return bool(self.flags & FLAG_DELTA)


def negotiate_version(peer_versions: Sequence[int]) -> int:
    """Highest wire version both ends speak (server calls this per client)."""
    common = set(SUPPORTED_VERSIONS) & set(int(v) for v in peer_versions)
    if not common:
        raise CodecError(
            f"no common wire version: we speak {SUPPORTED_VERSIONS}, "
            f"peer speaks {tuple(peer_versions)}"
        )
    return max(common)


# ---------------------------------------------------------------------------
# pytree <-> flat (path, leaf) list.  Wire trees are nested dict/list/tuple
# containers (what every model family's init() produces).  Container types
# are recorded in the path tags ('k' dict key, 'i' list index, 't' tuple
# index) so decode rebuilds the exact treedef — tuples stay tuples.
# ---------------------------------------------------------------------------


def _flatten(tree) -> List[Tuple[List[Any], Any]]:
    out: List[Tuple[List[Any], Any]] = []
    _walk(tree, [], out)
    return out


def _walk(node, prefix, out) -> None:
    # A module-level function, not a closure: a self-referencing nested
    # ``walk`` would hold ``out`` (and so every device leaf) in a reference
    # cycle until the garbage collector ran, keeping a whole model's codes
    # on the device after encode, digest or hot swap.
    if is_compressed(node):
        out.append((prefix, node))
    elif isinstance(node, dict):
        if not node:
            raise CodecError("empty dict container is not serializable")
        for k in sorted(node):  # jax tree order: sorted dict keys
            if not isinstance(k, str):
                raise CodecError(f"non-string dict key {k!r} in wire tree")
            _walk(node[k], prefix + [["k", k]], out)
    elif isinstance(node, (list, tuple)):
        if not node:
            raise CodecError("empty sequence container is not serializable")
        tag = "i" if isinstance(node, list) else "t"
        for j, v in enumerate(node):
            _walk(v, prefix + [[tag, j]], out)
    else:
        out.append((prefix, node))


class _Node:
    __slots__ = ("tag", "kids")

    def __init__(self, tag):
        self.tag = tag
        self.kids: Dict[Any, Any] = {}


def _unflatten(entries: List[Tuple[List[Any], Any]]):
    """Rebuild nested dicts/lists/tuples from tagged paths."""
    if not entries:
        return {}
    if not entries[0][0]:
        if len(entries) != 1:
            raise CodecError("multiple leaves with an empty path")
        return entries[0][1]
    root = _Node(entries[0][0][0][0])
    for parts, leaf in entries:
        node = root
        for depth, (tag, key) in enumerate(parts):
            if node.tag != tag:
                raise CodecError("inconsistent container tags in manifest")
            if depth == len(parts) - 1:
                node.kids[key] = leaf
            else:
                child = node.kids.get(key)
                if not isinstance(child, _Node):
                    child = _Node(parts[depth + 1][0])
                    node.kids[key] = child
                node = child

    def materialize(n):
        if not isinstance(n, _Node):
            return n
        if n.tag == "k":
            return {k: materialize(v) for k, v in n.kids.items()}
        try:
            seq = [materialize(n.kids[i]) for i in range(len(n.kids))]
        except KeyError as e:
            raise CodecError(f"missing sequence index in manifest: {e}") from e
        return seq if n.tag == "i" else tuple(seq)

    return materialize(root)


def _path_key(parts: List[Any]) -> str:
    return "/".join(str(v) for _, v in parts)


def tree_digest(tree) -> int:
    """crc32 fingerprint of a storage tree (paths + codes + PVT scalars).

    Delta payloads embed the digest of the base they were encoded against;
    decode verifies the receiver's base matches, so applying a delta to the
    wrong round's model is a loud `CodecError`, not silent corruption.
    """
    h = 0
    for parts, leaf in _flatten(tree):
        h = zlib.crc32(_path_key(parts).encode(), h)
        kind = _leaf_kind(leaf)
        if kind is not None:
            # strategy leaves: hash the canonical wire chunks (deterministic)
            meta, chunks = _LEAF_CODECS[kind][1](leaf, None)
            h = zlib.crc32(json.dumps(meta, separators=(",", ":"),
                                      sort_keys=True).encode(), h)
            for c in chunks:
                h = zlib.crc32(c, h)
        elif is_compressed(leaf):
            h = zlib.crc32(np.ascontiguousarray(_to_host(leaf.codes)).tobytes(), h)
            h = zlib.crc32(
                np.ascontiguousarray(_to_host(leaf.s, np.float32)).tobytes(), h
            )
            h = zlib.crc32(
                np.ascontiguousarray(_to_host(leaf.b, np.float32)).tobytes(), h
            )
            h = zlib.crc32(leaf.fmt.name.encode(), h)
        else:
            h = zlib.crc32(np.ascontiguousarray(_to_host(leaf)).tobytes(), h)
    return h


# ---------------------------------------------------------------------------
# per-leaf encoding
# ---------------------------------------------------------------------------


def _codes_np(cv: CompressedVariable) -> np.ndarray:
    return _to_host(cv.codes).reshape(-1)


@functools.partial(jax.jit, static_argnums=1)
def _split(x: jax.Array, size: int) -> List[jax.Array]:
    """``x`` flattened and cut into ``size``-element pieces, the last one
    ragged: one program per (shape, size), however many pieces."""
    flat = x.reshape(-1)
    return [flat[i:i + size] for i in range(0, flat.shape[0], size)]


_join = jax.jit(jnp.concatenate)  # one program per list of piece shapes

# Unpacked chunks are cast to their container and joined this many at a time:
# a program per group, not per chunk, and at most this many u32 chunks held.
_GROUP = 16


@functools.partial(jax.jit, static_argnums=1)
def _narrow_join(chunks: List[jax.Array], dtype) -> jax.Array:
    return jnp.concatenate([c.astype(dtype) for c in chunks])


def _pack_np(codes: jax.Array, bits: int) -> np.ndarray:
    """Device codes -> their packed u32 words on the host, read once."""
    words = []
    for piece in _split(codes, _CHUNK_FIELDS):
        with null_span(None, "codec.pack"):
            words.append(packing.pack(piece, bits))
    return _to_host(words[0] if len(words) == 1 else _join(words), np.uint32)


def _unpack_np(words: np.ndarray, bits: int, n: int) -> jax.Array:
    """Host u32 words -> ``n`` flat codes on the device in the container of
    ``bits``.  Chunks are cast by groups before the join, so the join holds
    about twice the codes' container bytes, not their u32 form."""
    dtype = uint_container(bits)
    step = _CHUNK_FIELDS * bits // 32  # words per full chunk
    pieces = _split(_to_device(words, jnp.uint32), step)
    groups, chunks = [], []
    for i, piece in zip(range(0, n, _CHUNK_FIELDS), pieces):
        with null_span(None, "codec.unpack"):
            chunks.append(packing.unpack(piece, bits, min(_CHUNK_FIELDS, n - i)))
        if len(chunks) == _GROUP or i + _CHUNK_FIELDS >= n:
            groups.append(_narrow_join(chunks, dtype))
            chunks = []
    del pieces  # the join holds only the codes
    return groups[0] if len(groups) == 1 else _join(groups)


def _encode_omc(cv: CompressedVariable, base) -> Tuple[Dict[str, Any], List[bytes]]:
    fmt = cv.fmt
    s = np.ascontiguousarray(_to_host(cv.s, np.float32))
    b = np.ascontiguousarray(_to_host(cv.b, np.float32))
    meta = dict(
        kind="omc",
        fmt=fmt.name,
        shape=list(cv.codes.shape),
        # np.ascontiguousarray promotes 0-d to 1-d — record the true shape
        # so scalar (per-tensor) PVT params survive the roundtrip and a
        # hot-swapped tree keeps the exact jit-cache signature
        sb_shape=list(np.shape(cv.s)),
        mode="full",
    )
    full_words = _pack_np(cv.codes, fmt.bits)
    chunks = [s.tobytes(), b.tobytes()]
    if (
        base is not None
        and is_compressed(base)
        and base.fmt == fmt
        and tuple(base.codes.shape) == tuple(cv.codes.shape)
    ):
        # the delta is found on the host: XOR against the base, np.nonzero
        xor = _codes_np(cv).astype(np.uint32) ^ _codes_np(base).astype(np.uint32)
        (idx,) = np.nonzero(xor)
        delta_bytes = 4 * idx.size + 4 * packing.packed_words(max(idx.size, 1), fmt.bits)
        if idx.size and delta_bytes < 4 * full_words.size:
            meta["mode"] = "delta"
            meta["nnz"] = int(idx.size)
            chunks.append(np.ascontiguousarray(idx.astype(np.uint32)).tobytes())
            chunks.append(_pack_np(_to_device(xor[idx]), fmt.bits).tobytes())
            return meta, chunks
        if idx.size == 0:
            meta["mode"] = "delta"
            meta["nnz"] = 0
            return meta, chunks
    chunks.append(full_words.tobytes())
    return meta, chunks


def _encode_raw(leaf, base) -> Tuple[Dict[str, Any], List[bytes]]:
    arr = np.ascontiguousarray(_to_host(leaf))
    meta = dict(
        kind="raw",
        dtype=arr.dtype.str,
        shape=list(arr.shape),
        mode="full",
    )
    if (
        base is not None
        and not is_compressed(base)
        and hasattr(base, "dtype")
        and np.dtype(base.dtype) == arr.dtype
        and np.shape(base) == arr.shape
        and arr.dtype.itemsize == 4
    ):
        xor = arr.view(np.uint32).reshape(-1) ^ np.ascontiguousarray(
            _to_host(base)
        ).view(np.uint32).reshape(-1)
        (idx,) = np.nonzero(xor)
        if 8 * idx.size < arr.nbytes:
            meta["mode"] = "delta"
            meta["nnz"] = int(idx.size)
            return meta, [
                np.ascontiguousarray(idx.astype(np.uint32)).tobytes(),
                np.ascontiguousarray(xor[idx]).tobytes(),
            ]
    return meta, [arr.tobytes()]


def _decode_omc(meta: Dict[str, Any], body: memoryview, off: int, base):
    fmt = FloatFormat.parse(meta["fmt"])
    shape = tuple(meta["shape"])
    sb_shape = tuple(meta.get("sb_shape", ()))
    n = int(np.prod(shape)) if shape else 1
    n_sb = int(np.prod(sb_shape)) if sb_shape else 1
    s = np.frombuffer(body, np.float32, n_sb, off).reshape(sb_shape)
    off += 4 * n_sb
    b = np.frombuffer(body, np.float32, n_sb, off).reshape(sb_shape)
    off += 4 * n_sb
    if meta["mode"] == "delta":
        if base is None or not is_compressed(base):
            raise CodecError(
                "delta leaf but no compressed base variable was supplied"
            )
        if base.fmt != fmt or tuple(base.codes.shape) != shape:
            raise CodecError("delta base mismatch (format or shape)")
        codes = _codes_np(base).astype(np.uint32).copy()
        nnz = int(meta["nnz"])
        if nnz:
            idx = np.frombuffer(body, np.uint32, nnz, off)
            off += 4 * nnz
            nwords = packing.packed_words(nnz, fmt.bits)
            words = np.frombuffer(body, np.uint32, nwords, off)
            off += 4 * nwords
            codes[idx] ^= _to_host(_unpack_np(words, fmt.bits, nnz))
        codes = codes.astype(np.dtype(fmt.container_dtype))
    else:
        nwords = packing.packed_words(n, fmt.bits)
        words = np.frombuffer(body, np.uint32, nwords, off)
        off += 4 * nwords
        codes = _unpack_np(words, fmt.bits, n)
    if not isinstance(codes, jax.Array):
        codes = _to_device(codes)
    cv = CompressedVariable(
        codes.reshape(shape).astype(fmt.container_dtype),
        _to_device(s.reshape(sb_shape), jnp.float32),
        _to_device(b.reshape(sb_shape), jnp.float32),
        fmt,
    )
    return cv, off


def _decode_raw(meta: Dict[str, Any], body: memoryview, off: int, base):
    dtype = np.dtype(meta["dtype"])
    shape = tuple(meta["shape"])
    n = int(np.prod(shape)) if shape else 1
    if meta["mode"] == "delta":
        if base is None or is_compressed(base):
            raise CodecError("delta leaf but no matching raw base was supplied")
        barr = np.ascontiguousarray(_to_host(base))
        if barr.dtype != dtype or barr.shape != shape:
            raise CodecError("delta base mismatch (dtype or shape)")
        bits = barr.view(np.uint32).reshape(-1).copy()
        nnz = int(meta["nnz"])
        if nnz:
            idx = np.frombuffer(body, np.uint32, nnz, off)
            off += 4 * nnz
            xor = np.frombuffer(body, np.uint32, nnz, off)
            off += 4 * nnz
            bits[idx] ^= xor
        arr = bits.view(dtype).reshape(shape)
    else:
        arr = np.frombuffer(body, dtype, n, off).reshape(shape)
        off += dtype.itemsize * n
    return _to_device(arr), off


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def encode_payload(tree, *, base=None, round_index: int = 0,
                   strategy=None) -> bytes:
    """Serialize a storage pytree to a wire payload.

    ``base`` (the tree the receiver already holds, e.g. the previous round's
    model) switches each leaf to sparse XOR-delta encoding when that is
    smaller; the receiver must then pass the same base to
    :func:`decode_payload`.

    ``strategy`` (a :class:`repro.compress.CompressionStrategy` instance or
    registered name) stamps the frame with the strategy tag + its wire
    version; payloads containing registered strategy leaves are stamped
    automatically.  Untagged frames (the plain OMC path) stay
    byte-identical to wire version 1 payloads.
    """
    with null_span(None, "codec.encode"):
        base_leaves: Dict[str, Any] = {}
        if base is not None:
            base_leaves = {_path_key(p): leaf for p, leaf in _flatten(base)}

        manifest: List[Dict[str, Any]] = []
        chunks: List[bytes] = []
        any_delta = False
        kinds_seen = set()
        for parts, leaf in _flatten(tree):
            bleaf = base_leaves.get(_path_key(parts))
            if is_compressed(leaf):
                meta, ch = _encode_omc(leaf, bleaf)
            elif (kind := _leaf_kind(leaf)) is not None:
                meta, ch = _LEAF_CODECS[kind][1](leaf, bleaf)
                kinds_seen.add(kind)
            else:
                meta, ch = _encode_raw(leaf, bleaf)
            any_delta |= meta["mode"] == "delta"
            meta["path"] = parts
            manifest.append(meta)
            chunks.extend(ch)

        frame: Dict[str, Any] = dict(leaves=manifest)
        tag = _strategy_tag(strategy, kinds_seen)
        if tag is not None:
            frame["strategy"], frame["strategy_version"] = tag
        mjson = json.dumps(frame, separators=(",", ":")).encode()
        body = b"".join(chunks)
        flags = FLAG_DELTA if any_delta else 0
        digest = tree_digest(base) if any_delta else 0
        crc = zlib.crc32(body, zlib.crc32(mjson))
        header = _HEADER.pack(
            MAGIC, WIRE_VERSION, flags, int(round_index), len(mjson), len(body),
            crc, digest,
        )
        return header + mjson + body


def _strategy_tag(strategy, kinds_seen) -> Optional[Tuple[str, int]]:
    """Resolve the frame's (strategy, wire_version) stamp, if any."""
    if strategy is not None:
        if isinstance(strategy, str):
            _ensure_strategy_codecs()
            from repro.compress import strategy_class

            cls = strategy_class(strategy)
            return cls.name, cls.wire_version
        return strategy.name, strategy.wire_version
    if kinds_seen:
        if len(kinds_seen) > 1:
            raise CodecError(
                f"tree mixes strategy leaf kinds {sorted(kinds_seen)}; pass "
                f"strategy= explicitly to tag the frame"
            )
        _ensure_strategy_codecs()
        from repro.compress import strategy_class

        cls = strategy_class(next(iter(kinds_seen)))
        return cls.name, cls.wire_version
    return None


def _parse_frame(data: bytes) -> Tuple[PayloadInfo, Dict[str, Any], memoryview]:
    """Validate framing + checksum; parse the manifest exactly once."""
    if len(data) < _HEADER.size:
        raise CodecError(f"payload truncated: {len(data)} bytes")
    magic, ver, flags, rnd, mlen, blen, crc, digest = _HEADER.unpack_from(data)
    if magic != MAGIC:
        raise CodecError(f"bad magic {magic!r}")
    if ver not in SUPPORTED_VERSIONS:
        raise CodecError(
            f"unsupported wire version {ver}; supported: {SUPPORTED_VERSIONS}"
        )
    if len(data) != _HEADER.size + mlen + blen:
        raise CodecError(
            f"length mismatch: header says {_HEADER.size + mlen + blen}, "
            f"got {len(data)}"
        )
    mview = memoryview(data)
    payload = mview[_HEADER.size:]
    if zlib.crc32(payload) != crc:
        raise CodecError("checksum mismatch: payload corrupt")
    try:
        manifest = json.loads(bytes(payload[:mlen]).decode())
        leaves = manifest["leaves"]
    except Exception as e:  # malformed manifest despite valid crc framing
        raise CodecError(f"malformed manifest: {e}") from e
    _check_strategy_tag(manifest)
    info = PayloadInfo(
        version=ver,
        flags=flags,
        round_index=rnd,
        header_bytes=_HEADER.size + mlen,
        body_bytes=blen,
        total_bytes=len(data),
        num_leaves=len(leaves),
        num_compressed=sum(1 for l in leaves if l["kind"] != "raw"),
        num_delta=sum(1 for l in leaves if l["mode"] == "delta"),
        base_digest=digest,
        strategy=manifest.get("strategy"),
        strategy_version=int(manifest.get("strategy_version", 0)),
    )
    return info, manifest, mview[info.header_bytes :]


def peek_payload(data: bytes) -> PayloadInfo:
    """Validate framing + checksum and return sizes, without decoding."""
    return _parse_frame(data)[0]


def header_base_digest(data: bytes) -> int:
    """Base digest straight from the header — no checksum scan.  For cheap
    delta-vs-full routing decisions; integrity is still enforced at decode."""
    if len(data) < _HEADER.size:
        raise CodecError(f"payload truncated: {len(data)} bytes")
    magic, _, flags, _, _, _, _, digest = _HEADER.unpack_from(data)
    if magic != MAGIC:
        raise CodecError(f"bad magic {magic!r}")
    return digest if flags & FLAG_DELTA else 0


def decode_payload(data: bytes, *, base=None) -> Tuple[Any, PayloadInfo]:
    """Payload bytes -> (storage pytree, PayloadInfo).  Bit-exact inverse of
    :func:`encode_payload`.

    Delta payloads require the encoder's ``base`` and verify it by digest —
    supplying a different tree (e.g. the wrong round's model) raises
    `CodecError` instead of silently producing corrupt parameters.  For full
    payloads ``base`` is ignored, so callers may always pass what they hold.
    """
    with null_span(None, "codec.decode"):
        info, manifest, body = _parse_frame(data)
        if info.is_delta:
            if base is None:
                raise CodecError(
                    "delta payload requires the base tree it was built on"
                )
            if tree_digest(base) != info.base_digest:
                raise CodecError(
                    "delta base mismatch: payload was encoded against a "
                    "different tree than the one supplied (stale or "
                    "wrong-round base)"
                )
        base_leaves: Dict[str, Any] = {}
        if base is not None:
            base_leaves = {_path_key(p): leaf for p, leaf in _flatten(base)}

        entries = []
        off = 0
        for meta in manifest["leaves"]:
            parts = [list(p) for p in meta["path"]]
            bleaf = base_leaves.get(_path_key(parts))
            if meta["kind"] == "omc":
                leaf, off = _decode_omc(meta, body, off, bleaf)
            elif meta["kind"] == "raw":
                leaf, off = _decode_raw(meta, body, off, bleaf)
            else:
                if meta["kind"] not in _LEAF_CODECS:
                    _ensure_strategy_codecs()
                if meta["kind"] not in _LEAF_CODECS:
                    raise CodecError(f"unknown leaf kind {meta['kind']!r}")
                leaf, off = _LEAF_CODECS[meta["kind"]][2](meta, body, off, bleaf)
            entries.append((parts, leaf))
        if off != info.body_bytes:
            raise CodecError(f"body length mismatch: consumed {off}, "
                             f"have {info.body_bytes}")
        return _unflatten(entries), info


def payload_bytes_report(tree) -> Dict[str, Any]:
    """Theoretical full-payload body size for a storage tree.

    Uses the exact accounting the store layer uses (``packed_bytes`` + 8
    bytes of PVT scalars per entry for ``omc`` leaves, each strategy leaf's
    ``wire_body_bytes`` otherwise), so for any tree
    ``payload_bytes_report(t)["wire_bytes"] ==
    state_bytes_report(t)["packed_bytes"]`` (pure OMC trees) and a
    serialized full payload's ``body_bytes`` equals it for every strategy.

    ``per_strategy`` breaks the body down by leaf kind — payload bytes,
    index bytes (positions), and metadata bytes (PVT / scale scalars) —
    the rows wire-accounting reconciliation tests assert against
    (DESIGN.md §11).
    """
    wire = fp32 = n_params = n_comp = 0
    per: Dict[str, Dict[str, int]] = {}

    def bucket(kind: str) -> Dict[str, int]:
        return per.setdefault(kind, dict(
            payload_bytes=0, index_bytes=0, meta_bytes=0,
            num_leaves=0, num_params=0,
        ))

    for _, leaf in _flatten(tree):
        if is_compressed(leaf):
            n = int(leaf.codes.size)
            meta = _PVT_BYTES_PER_ENTRY * int(np.asarray(leaf.s).size)
            body = packing.packed_bytes(n, leaf.fmt) + meta
            n_comp += n
            b = bucket("omc")
            b["meta_bytes"] += meta
        elif (kind := _leaf_kind(leaf)) is not None:
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            body = int(leaf.wire_body_bytes())
            n_comp += n
            b = bucket(kind)
            b["index_bytes"] += int(leaf.index_bytes())
            b["meta_bytes"] += int(leaf.meta_bytes())
        else:
            arr = np.asarray(leaf)
            n = int(arr.size)
            body = int(arr.nbytes)
            b = bucket("raw")
        n_params += n
        fp32 += 4 * n
        wire += body
        b["payload_bytes"] += body
        b["num_leaves"] += 1
        b["num_params"] += n
    return dict(
        num_params=n_params,
        num_compressed=n_comp,
        fp32_bytes=fp32,
        wire_bytes=wire,
        wire_ratio=wire / max(fp32, 1),
        per_strategy=per,
    )
