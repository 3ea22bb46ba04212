"""Versioned host-side KV block images — ONE wire format for
preemption swap and fleet KV shipping (the disaggregation round).

Two paths copy paged KV between device pools and host memory:

* **preemption swap** (serve/paged.py ``swap_out``/``swap_in``) — a
  preempted request's blocks round-trip through host RAM and resume
  byte-exactly;
* **KV shipping** (serve/fleet.py disaggregated serving) — a prefill
  specialist's canonical prompt blocks travel to a decode specialist's
  pool, seeding its radix prefix cache so the admission lands warm.

Before this module the swap image was a bare ``(kc_host, vc_host)``
numpy-pytree pair with no self-description: nothing stopped a drifted
producer (or a truncated transfer) from scattering garbage into a
live pool.  A :class:`KVImage` carries a VERSION, the block geometry,
the quantization flag, and a per-leaf dtype/shape header captured at
pack time; :meth:`KVImage.validate` re-derives the signature from the
arrays and cross-checks it against both the header (truncation /
mutation fails typed) and the consuming arena's geometry (a dense
image cannot scatter into an int8 pool, a block-size-16 image cannot
land in a block-size-32 pool).  Both swap and ship consume images
through the same checks, so the two paths cannot drift.

Leaf layout (the cache-row convention every fixed-shape copy in
serve/paged.py uses): dense pools are one ``(L, 1, H_kv, W, D)``
array per K/V; int8 pools are ``(values, scales)`` tuples whose
scales leaf drops the trailing ``D`` axis.  ``W`` is the image's lane
width — a FULL row for swap (the historical shape, one executable per
engine geometry) or the narrow ``n_data * block_size`` slice for
shipping (ship bytes track the prompt, not ``max_len``).

Since the multi-host round the image is also the WIRE format: every
image carries a crc32 ``checksum`` over its leaf bytes (captured at
pack time, re-derived in :meth:`KVImage.validate` — a bit-flip that
preserves shape and dtype fails typed, which the header check alone
cannot catch), and :meth:`KVImage.to_bytes` /
:meth:`KVImage.from_bytes` frame it for a socket: magic + version +
pickled metadata + raw leaf bytes + the checksum.  ``from_bytes``
rejects truncation (mid-stream EOF), corruption (checksum mismatch)
and version skew with :class:`KVImageError` BEFORE any array is
handed to a pool.
"""

from __future__ import annotations

import pickle
import struct
import zlib

import numpy as np

__all__ = ["KVIMAGE_VERSION", "KVImage", "KVImageError", "pack_image",
           "leaf_list"]

#: bump when the leaf layout or header schema changes; ``validate``
#: refuses images from a different version rather than guessing
KVIMAGE_VERSION = 1

#: wire framing for ``to_bytes``/``from_bytes``: magic, u16 version,
#: u8 quant, u32 metadata length (then metadata, leaf bytes, crc32)
_WIRE_MAGIC = b"KVIM"
_WIRE_HEAD = struct.Struct("!4sHBI")
_WIRE_CRC = struct.Struct("!I")


class KVImageError(ValueError):
    """A KV image failed validation (version / geometry / dtype /
    header mismatch, or arrays inconsistent with their pack-time
    header).  Raised BEFORE any scatter touches a pool — a bad image
    degrades to a cold prefill, never to corrupted cache state."""


def _leaf_list(tree):
    """Flatten a host cache pytree (array, or (values, scales) tuple,
    possibly nested under tuples/lists) into a leaf list in
    deterministic order."""
    if isinstance(tree, (tuple, list)):
        out = []
        for t in tree:
            out.extend(_leaf_list(t))
        return out
    return [tree]


def _signature(kc, vc):
    """Per-leaf (shape, dtype) header, K leaves then V leaves."""
    return tuple((tuple(a.shape), str(a.dtype))
                 for a in _leaf_list(kc) + _leaf_list(vc))


def leaf_list(tree):
    """Public alias of the leaf flattening (K leaves then V leaves is
    ``leaf_list(kc) + leaf_list(vc)``) — the dist ship path frames
    images leaf-by-leaf and must slice in the exact order the
    checksum covers."""
    return _leaf_list(tree)


def _checksum(kc, vc) -> int:
    """crc32 over every leaf's raw bytes, K leaves then V leaves —
    the content integrity the shape/dtype header cannot see."""
    crc = 0
    for a in _leaf_list(kc) + _leaf_list(vc):
        crc = zlib.crc32(np.ascontiguousarray(a).data, crc)
    return crc & 0xFFFFFFFF


class KVImage:
    """One request's (or prefix's) KV blocks as a self-describing host
    image.  Construct through :func:`pack_image` — the header is
    captured from the arrays at pack time, which is what makes
    later truncation detectable."""

    __slots__ = ("version", "block_size", "n_data", "quant", "header",
                 "kc", "vc", "checksum")

    def __init__(self, version, block_size, n_data, quant, header,
                 kc, vc, checksum=None):
        self.version = int(version)
        self.block_size = int(block_size)
        self.n_data = int(n_data)
        self.quant = bool(quant)
        self.header = tuple(header)
        self.kc = kc
        self.vc = vc
        # crc32 over the leaf bytes (None on images packed by callers
        # predating the wire round: validate then skips the content
        # check and keeps the header/geometry checks)
        self.checksum = (None if checksum is None
                         else int(checksum) & 0xFFFFFFFF)

    @property
    def width(self) -> int:
        """Lane width of the image rows (positions per leaf)."""
        return int(_leaf_list(self.kc)[0].shape[3])

    @property
    def nbytes(self) -> int:
        """Host bytes the image's arrays occupy — the fleet's
        ``serve.fleet.ship_bytes`` accounting."""
        return int(sum(a.nbytes
                       for a in _leaf_list(self.kc)
                       + _leaf_list(self.vc)))

    def validate(self, block_size, quant, pool_k=None, head_dim=None):
        """Typed validation before any scatter: version supported,
        geometry matches the consuming arena (``block_size``,
        ``quant``), arrays consistent with the pack-time header
        (truncated or mutated images fail HERE), lane width a block
        multiple covering ``n_data`` blocks, and — when the consuming
        pool's K leaves are handed in — per-leaf dtype, layers and
        row width (H_kv·D of a pool row; ``head_dim`` pins D itself)
        against the pool.  Raises
        :class:`KVImageError`; returns None."""
        if self.version != KVIMAGE_VERSION:
            raise KVImageError(
                f"KV image version {self.version} != supported "
                f"{KVIMAGE_VERSION}: refuse rather than guess at the "
                f"leaf layout")
        if self.block_size != block_size:
            raise KVImageError(
                f"KV image block_size ({self.block_size}) != pool "
                f"block_size ({block_size}): lanes would not tile "
                f"the target blocks")
        if self.quant != bool(quant):
            raise KVImageError(
                f"KV image quant={self.quant} vs pool quant="
                f"{bool(quant)}: dense and int8 (values, scales) "
                f"layouts are not interchangeable")
        sig = _signature(self.kc, self.vc)
        if sig != self.header:
            raise KVImageError(
                "KV image arrays do not match their pack-time header "
                "(truncated or mutated in transit): "
                f"header={self.header} got={sig}")
        if self.checksum is not None:
            crc = _checksum(self.kc, self.vc)
            if crc != self.checksum:
                raise KVImageError(
                    f"KV image payload corrupted in transit: crc32 "
                    f"{crc:#010x} != packed {self.checksum:#010x} — "
                    f"a shape-preserving bit-flip the header check "
                    f"cannot see; refuse before any scatter")
        k_leaves = _leaf_list(self.kc)
        v_leaves = _leaf_list(self.vc)
        if len(k_leaves) != len(v_leaves):
            raise KVImageError(
                f"KV image K/V leaf-count mismatch "
                f"({len(k_leaves)} vs {len(v_leaves)})")
        W = self.width
        if W % self.block_size != 0:
            raise KVImageError(
                f"KV image lane width ({W}) is not a multiple of "
                f"block_size ({self.block_size})")
        if self.n_data < 0 or self.n_data * self.block_size > W:
            raise KVImageError(
                f"KV image n_data ({self.n_data} blocks) exceeds its "
                f"own lane width ({W} positions): a length-lying "
                f"image must never scatter")
        for a in k_leaves + v_leaves:
            if a.ndim < 4 or a.shape[1] != 1 or a.shape[3] != W:
                raise KVImageError(
                    f"KV image leaf shape {tuple(a.shape)} is not a "
                    f"(L, 1, H, {W}[, D]) cache row")
        if pool_k is not None:
            pool_leaves = _leaf_list(pool_k)
            if len(pool_leaves) != len(k_leaves):
                raise KVImageError(
                    f"KV image has {len(k_leaves)} K leaves but the "
                    f"pool has {len(pool_leaves)} (dense vs int8 "
                    f"layout drift)")
            for img, pool in zip(k_leaves, pool_leaves):
                # pool: (L, N+1, B, H[·D]) vs image: (L, 1, H, W[, D])
                width = img.shape[2] * int(np.prod(img.shape[4:]))
                if (img.shape[0] != pool.shape[0]
                        or width != pool.shape[3]
                        or (head_dim is not None
                            and img.shape[4:] not in ((), (head_dim,)))
                        or str(img.dtype) != str(pool.dtype)):
                    raise KVImageError(
                        f"KV image leaf {tuple(img.shape)}/{img.dtype}"
                        f" incompatible with pool leaf "
                        f"{tuple(pool.shape)}/{pool.dtype} (layer/"
                        f"head/head-dim/dtype must match)")

    def narrowed(self, n_data=None) -> "KVImage":
        """A copy of this image sliced to ``n_data`` blocks' lanes
        (default: ``self.n_data``) — the ship-path form, where bytes
        on the wire track the shipped prefix, not ``max_len``.  The
        header is re-captured from the sliced arrays (a narrowed
        image is a NEW image, not a mutation of this one)."""
        n = self.n_data if n_data is None else int(n_data)
        if n > self.n_data:
            raise KVImageError(
                f"narrowed({n}) beyond the image's n_data "
                f"({self.n_data})")
        w = max(n, 1) * self.block_size

        def cut(tree):
            if isinstance(tree, tuple):
                return tuple(cut(t) for t in tree)
            if isinstance(tree, list):
                return [cut(t) for t in tree]
            return np.ascontiguousarray(tree[:, :, :, :w])

        kc, vc = cut(self.kc), cut(self.vc)
        return KVImage(self.version, self.block_size, n, self.quant,
                       _signature(kc, vc), kc, vc,
                       checksum=_checksum(kc, vc))

    # -- wire codec (the dist transport's KV payload) --------------------
    def to_bytes(self) -> bytes:
        """Frame the image for a socket: magic + version + quant +
        length-prefixed metadata (geometry + per-leaf header), the raw
        leaf bytes in header order, and a trailing crc32 over the leaf
        bytes.  Decode with :meth:`from_bytes`."""
        leaves = [np.ascontiguousarray(a)
                  for a in _leaf_list(self.kc) + _leaf_list(self.vc)]
        meta = pickle.dumps(
            {"block_size": self.block_size, "n_data": self.n_data,
             "header": self.header,
             "k_leaves": len(_leaf_list(self.kc))},
            protocol=pickle.HIGHEST_PROTOCOL)
        crc = 0
        chunks = [_WIRE_HEAD.pack(_WIRE_MAGIC, self.version,
                                  int(self.quant), len(meta)), meta]
        for a in leaves:
            crc = zlib.crc32(a.data, crc)
            chunks.append(a.tobytes())
        chunks.append(_WIRE_CRC.pack(crc & 0xFFFFFFFF))
        return b"".join(chunks)

    @classmethod
    def from_bytes(cls, buf) -> "KVImage":
        """Decode a :meth:`to_bytes` frame.  Every malformed input is
        a typed :class:`KVImageError`: short buffers (mid-stream EOF),
        bad magic, version skew, and payload whose crc32 disagrees
        with the trailer (bit-flip in transit).  The returned image
        still goes through :meth:`validate` at the consuming pool —
        this decoder checks the WIRE, validate checks the POOL."""
        buf = memoryview(bytes(buf))
        if len(buf) < _WIRE_HEAD.size + _WIRE_CRC.size:
            raise KVImageError(
                f"KV image wire frame truncated: {len(buf)} bytes is "
                f"shorter than the fixed framing "
                f"({_WIRE_HEAD.size + _WIRE_CRC.size})")
        magic, version, quant, meta_len = _WIRE_HEAD.unpack_from(buf, 0)
        if magic != _WIRE_MAGIC:
            raise KVImageError(
                f"KV image wire frame has bad magic {bytes(magic)!r} "
                f"(expected {_WIRE_MAGIC!r}): not a KV image")
        if version != KVIMAGE_VERSION:
            raise KVImageError(
                f"KV image wire version {version} != supported "
                f"{KVIMAGE_VERSION}: refuse rather than guess at the "
                f"leaf layout")
        off = _WIRE_HEAD.size
        if len(buf) < off + meta_len + _WIRE_CRC.size:
            raise KVImageError(
                f"KV image wire frame truncated inside metadata "
                f"({len(buf)} bytes, metadata needs "
                f"{off + meta_len + _WIRE_CRC.size})")
        try:
            meta = pickle.loads(bytes(buf[off:off + meta_len]))
            header = tuple(tuple(h) for h in meta["header"])
            k_leaves = int(meta["k_leaves"])
            block_size, n_data = meta["block_size"], meta["n_data"]
        except Exception as e:
            raise KVImageError(
                f"KV image wire metadata undecodable ({e!r})") from e
        off += meta_len
        leaves = []
        for shape, dtype in header:
            n = int(np.prod(shape, dtype=np.int64)) \
                * np.dtype(dtype).itemsize
            if len(buf) < off + n + _WIRE_CRC.size:
                raise KVImageError(
                    f"KV image wire frame truncated mid-leaf: leaf "
                    f"{len(leaves)} needs {n} bytes, "
                    f"{len(buf) - off - _WIRE_CRC.size} remain "
                    f"(mid-stream EOF)")
            leaves.append(np.frombuffer(
                buf[off:off + n], dtype=dtype).reshape(shape))
            off += n
        if len(buf) != off + _WIRE_CRC.size:
            raise KVImageError(
                f"KV image wire frame has {len(buf) - off - _WIRE_CRC.size}"
                f" trailing bytes beyond its header's leaves (length-"
                f"lying frame)")
        (want,) = _WIRE_CRC.unpack_from(buf, off)
        crc = 0
        for a in leaves:
            crc = zlib.crc32(a.data, crc)
        crc &= 0xFFFFFFFF
        if crc != want:
            raise KVImageError(
                f"KV image wire payload corrupted: crc32 {crc:#010x} "
                f"!= trailer {want:#010x} (bit-flip in transit)")
        if not 0 < k_leaves < len(leaves) or k_leaves * 2 != len(leaves):
            raise KVImageError(
                f"KV image wire metadata claims {k_leaves} K leaves "
                f"of {len(leaves)} total — K/V must split evenly")

        def tree(ls):
            return ls[0] if len(ls) == 1 else tuple(ls)

        return cls(version, block_size, n_data, bool(quant), header,
                   tree(leaves[:k_leaves]), tree(leaves[k_leaves:]),
                   checksum=want)


def pack_image(kc_host, vc_host, block_size, n_data, quant) -> KVImage:
    """Seal host cache-row pytrees into a :class:`KVImage`.  The
    per-leaf header is captured HERE, so any later divergence between
    the arrays and what was packed (a truncated transfer, an in-place
    mutation) fails :meth:`KVImage.validate` typed.  Since the wire
    round the content crc32 is captured too — shape-preserving
    corruption fails the same way."""
    return KVImage(KVIMAGE_VERSION, block_size, n_data, quant,
                   _signature(kc_host, vc_host), kc_host, vc_host,
                   checksum=_checksum(kc_host, vc_host))
