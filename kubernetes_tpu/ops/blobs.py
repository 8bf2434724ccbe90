"""Blob codec: many small feature arrays <-> two dense transfer buffers.

Every host->device transfer pays a fixed per-array cost, and a
ClusterTensors/PodFeatures pytree has ~25/~55 leaves, which would dominate the
per-cycle budget. Instead the host packs all fields of a struct into ONE f32
blob and ONE i32 blob (bools stored as i32), ships two arrays, and the jitted
pipeline unpacks them with slices/reshapes that XLA folds away.

The codec is schema-driven: field name -> (shape, kind). Schemas are derived
from Capacities so pack/unpack stay in lockstep with the dataclasses in
ops.features.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

Kind = str  # "f32" | "i32" | "bool"


@jax.tree_util.register_dataclass
@dataclass
class Blobs:
    """The two transfer buffers. Leading batch axes allowed."""

    f32: jax.Array
    i32: jax.Array


class BlobCodec:
    def __init__(self, schema: dict[str, tuple[tuple[int, ...], Kind]]):
        self.schema = schema
        self._subset_cache: dict[tuple, tuple] = {}
        self._f32_off: dict[str, tuple[int, int]] = {}
        self._i32_off: dict[str, tuple[int, int]] = {}
        f = i = 0
        for name, (shape, kind) in schema.items():
            size = math.prod(shape) if shape else 1
            if kind == "f32":
                self._f32_off[name] = (f, size)
                f += size
            else:  # i32 / bool
                self._i32_off[name] = (i, size)
                i += size
        self.f32_size = f
        self.i32_size = i

    def alloc(self, *batch: int) -> tuple[np.ndarray, np.ndarray]:
        return (np.zeros(batch + (self.f32_size,), np.float32),
                np.zeros(batch + (self.i32_size,), np.int32))

    def pack_into(self, out_f32: np.ndarray, out_i32: np.ndarray,
                  fields: dict[str, np.ndarray]) -> None:
        """Write one struct's fields into (already-allocated) blob rows.
        out_* may be views (e.g. one batch row)."""
        for name, arr in fields.items():
            shape, kind = self.schema[name]
            if kind == "f32":
                off, size = self._f32_off[name]
                out_f32[..., off:off + size] = np.asarray(arr, np.float32).reshape(
                    arr.shape[: arr.ndim - len(shape)] + (size,)) if shape else arr
            else:
                off, size = self._i32_off[name]
                flat = (np.asarray(arr, np.int32).reshape(
                    arr.shape[: arr.ndim - len(shape)] + (size,)) if shape else arr)
                out_i32[..., off:off + size] = flat

    def pack(self, fields: dict[str, np.ndarray]) -> Blobs:
        f32, i32 = self.alloc()
        self.pack_into(f32, i32, fields)
        return Blobs(f32=jnp.asarray(f32), i32=jnp.asarray(i32))

    # ------------- field-subset transfers -------------
    #
    # A launch only reads the fields its active features touch; shipping the
    # full schema wastes most of the host->device link (e.g. a
    # no-affinity pod's selector arrays are ~90% of its row, and the host
    # packs every byte it ships). A subset blob packs just the named
    # fields (schema order); the device splices the rest in from a 1-row
    # full-schema template, broadcast over the batch — XLA
    # dead-code-eliminates the broadcasts nothing reads.

    def subset_layout(self, names: tuple[str, ...]):
        """(f32_offsets, i32_offsets, f32_size, i32_size) of a packed blob
        holding only `names`, laid out in schema order."""
        key = tuple(sorted(names))
        lay = self._subset_cache.get(key)
        if lay is not None:
            return lay
        unknown = [n for n in names if n not in self.schema]
        if unknown:
            # a typo'd subset name would otherwise silently ride the
            # template defaults — a silent-wrong-results failure mode
            raise KeyError(f"subset names not in schema: {unknown}")
        f_off: dict[str, tuple[int, int]] = {}
        i_off: dict[str, tuple[int, int]] = {}
        f = i = 0
        for name, (shape, kind) in self.schema.items():
            if name not in names:
                continue
            size = math.prod(shape) if shape else 1
            if kind == "f32":
                f_off[name] = (f, size)
                f += size
            else:
                i_off[name] = (i, size)
                i += size
        lay = (f_off, i_off, f, i)
        self._subset_cache[key] = lay
        return lay

    def alloc_subset(self, names: tuple[str, ...], *batch: int):
        _, _, fs, isz = self.subset_layout(names)
        return (np.zeros(batch + (fs,), np.float32),
                np.zeros(batch + (isz,), np.int32))

    def pack_into_subset(self, names: tuple[str, ...], out_f32: np.ndarray,
                         out_i32: np.ndarray,
                         fields: dict[str, np.ndarray]) -> None:
        """pack_into against a subset layout; fields outside it are skipped
        (their template defaults stand in on device)."""
        f_off, i_off, _, _ = self.subset_layout(names)
        for name, arr in fields.items():
            shape, kind = self.schema[name]
            if kind == "f32":
                if name not in f_off:
                    continue
                off, size = f_off[name]
                out_f32[..., off:off + size] = (
                    np.asarray(arr, np.float32).reshape(
                        arr.shape[: arr.ndim - len(shape)] + (size,))
                    if shape else arr)
            else:
                if name not in i_off:
                    continue
                off, size = i_off[name]
                out_i32[..., off:off + size] = (
                    np.asarray(arr, np.int32).reshape(
                        arr.shape[: arr.ndim - len(shape)] + (size,))
                    if shape else arr)

    def subset_template(self, names: tuple[str, ...], tmpl_f32: np.ndarray,
                        tmpl_i32: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Subset-layout rows sliced out of packed full-schema rows — the
        host-side base a subset batch pack starts from."""
        f_off, i_off, fs, isz = self.subset_layout(names)
        sf = np.zeros((fs,), np.float32)
        si = np.zeros((isz,), np.int32)
        for name, (off, size) in f_off.items():
            foff, _ = self._f32_off[name]
            sf[off:off + size] = tmpl_f32[foff:foff + size]
        for name, (off, size) in i_off.items():
            ioff, _ = self._i32_off[name]
            si[off:off + size] = tmpl_i32[ioff:ioff + size]
        return sf, si

    def unpack_subset(self, blobs: Blobs, names: tuple[str, ...],
                      template: Blobs, cls=None):
        """Subset blobs + a 1-row full-schema template blob for the absent
        fields, broadcast over the batch (inside jit: free)."""
        f_off, i_off, _, _ = self.subset_layout(names)
        batch = blobs.i32.shape[:-1]
        out = {}
        for name, (shape, kind) in self.schema.items():
            sub_off = f_off if kind == "f32" else i_off
            if name in sub_off:
                src = blobs.f32 if kind == "f32" else blobs.i32
                off, size = sub_off[name]
                arr = jax.lax.slice_in_dim(src, off, off + size, axis=-1)
                arr = arr.reshape(batch + shape) if shape else arr.reshape(batch)
            else:
                full_off = self._f32_off if kind == "f32" else self._i32_off
                tsrc = template.f32 if kind == "f32" else template.i32
                off, size = full_off[name]
                arr = jax.lax.slice_in_dim(tsrc, off, off + size, axis=-1)
                arr = jnp.broadcast_to(arr.reshape(shape), batch + shape)
            if kind == "bool":
                arr = arr != 0
            out[name] = arr
        return cls(**out) if cls is not None else out

    def unpack(self, blobs: Blobs, cls=None):
        """Slice the blobs back into named arrays (inside jit: free).
        Leading batch axes of the blobs are preserved on every field."""
        out = {}
        for name, (shape, kind) in self.schema.items():
            if kind == "f32":
                off, size = self._f32_off[name]
                arr = jax.lax.slice_in_dim(blobs.f32, off, off + size, axis=-1)
            else:
                off, size = self._i32_off[name]
                arr = jax.lax.slice_in_dim(blobs.i32, off, off + size, axis=-1)
            batch = arr.shape[:-1]
            arr = arr.reshape(batch + shape) if shape else arr.reshape(batch)
            if kind == "bool":
                arr = arr != 0
            out[name] = arr
        return cls(**out) if cls is not None else out
