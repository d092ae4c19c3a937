"""Block-quantized codecs for the compression tier (EQuARX,
arxiv.org/pdf/2506.17615).

A quantized payload is ``(codes: uint8, scales: float32)`` over fixed-size
blocks of the flattened input:

- ``int8``  — symmetric per-block scaling to [-127, 127]; 1 byte/element
  + 4/block bytes of scales (~3.94x smaller than fp32 at block=256).
- ``fp8``   — e4m3 emulation via ``ml_dtypes.float8_e4m3fn`` (the numpy
  dtype jax itself depends on): per-block scaling maps the block amax to
  the e4m3 max (448), then a saturating cast; 1 byte/element.
- ``bf16``  — a plain dtype narrowing (no scales); 2 bytes/element. Not a
  block codec, but resolving here lets ``grad_sync_compression="bf16"``
  ride the same wire plumbing as the quantized tiers.

The codecs are **pure numpy** so the CollectiveStore actor (the CPU-tier
reduce point) can dequant-accumulate without importing jax. There is no
on-device (ICI) quantized collective: the device step's gradient sync is
the GSPMD program's own reduce-scatter, in the gradients' dtype.

Error feedback (:class:`ErrorFeedback`): quantization error is *carried*,
not lost — the caller adds the residual before encoding and stores
``compensated - dequant(encode(compensated))`` for the next step, which is
what keeps quantized SGD/adam trajectories near the fp32 one (the
convergence test pins PPO int8 within 2% of fp32).

Non-finite inputs: scales are always finite — NaN entries encode as 0 and
±inf entries saturate to the block's finite amax (a gradient containing
them is already broken; the codec must not poison the whole block's scale,
and a NaN scale would corrupt every element of the block on decode).

When NOT to quantize (see collective/QUANT.md): normalization statistics
and other few-float control values (quantization error is O(value) while
the payload is already tiny), momentum-free accumulators that feed
comparisons, and any leg whose consumer needs bitwise determinism across
code versions. Compression is strictly opt-in everywhere.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

FP8_MAX = 448.0  # ml_dtypes.float8_e4m3fn finite max
DEFAULT_BLOCK = 256

_CODEC_NAMES = ("int8", "fp8", "bf16")


@dataclass(frozen=True)
class QuantCodec:
    """One codec choice: name + block size (block ignored for bf16)."""

    name: str
    block: int = DEFAULT_BLOCK

    def __post_init__(self):
        if self.name not in _CODEC_NAMES:
            raise ValueError(
                f"unknown codec {self.name!r} (one of {_CODEC_NAMES})")
        if self.block <= 0:
            raise ValueError(f"block must be positive, got {self.block}")

    @property
    def bytes_per_element(self) -> float:
        if self.name == "bf16":
            return 2.0
        return 1.0 + 4.0 / self.block  # codes + fp32 scale share

    def spec(self) -> str:
        return f"{self.name}:{self.block}"


def resolve_codec(compression: Any) -> Optional[QuantCodec]:
    """Normalize a user-facing ``compression`` knob into a codec.

    Accepts None / "none" (off), "int8" / "fp8" / "bf16", an
    "int8:128"-style spec with an explicit block size, or a QuantCodec.
    """
    if compression is None:
        return None
    if isinstance(compression, QuantCodec):
        return compression
    if not isinstance(compression, str):
        raise TypeError(f"compression must be a string or QuantCodec, "
                        f"got {type(compression).__name__}")
    s = compression.strip().lower()
    if s in ("", "none", "off", "fp32"):
        return None
    if ":" in s:
        name, _, block = s.partition(":")
        return QuantCodec(name, int(block))
    return QuantCodec(s)


@dataclass
class QuantizedTensor:
    """One encoded array: flat uint8 codes + per-block fp32 scales."""

    codec: str
    block: int
    shape: Tuple[int, ...]
    dtype: str  # original dtype str (decode target)
    codes: np.ndarray  # uint8, flat (padded to a whole number of blocks)
    scales: np.ndarray  # float32, one per block (empty for bf16)

    @property
    def wire_nbytes(self) -> int:
        return int(self.codes.nbytes + self.scales.nbytes)

    @property
    def raw_nbytes(self) -> int:
        n = int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1
        return n * np.dtype(self.dtype).itemsize

    def meta(self) -> Dict[str, Any]:
        return {"codec": self.codec, "block": self.block,
                "shape": list(self.shape), "dtype": self.dtype,
                "nscales": int(self.scales.size)}


def _sanitize_blocks(xb: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Finite-safe (values, amax): NaN -> 0; ±inf saturates to the finite
    amax of its block so one bad element cannot blow up the block scale."""
    finite = np.isfinite(xb)
    if finite.all():
        return xb, np.abs(xb).max(axis=-1)
    xf = np.where(finite, xb, np.float32(0.0))
    amax = np.abs(xf).max(axis=-1)
    cap = np.where(amax > 0, amax, np.float32(1.0))[..., None]
    xf = np.where(np.isnan(xb), np.float32(0.0),
                  np.clip(xb, -cap, cap)).astype(np.float32)
    return xf, np.abs(xf).max(axis=-1)


def _to_blocks(arr: np.ndarray, block: int) -> Tuple[np.ndarray, int]:
    flat = np.ascontiguousarray(arr, dtype=np.float32).reshape(-1)
    n = flat.size
    nb = max(1, -(-n // block))
    padded = np.zeros(nb * block, np.float32)
    padded[:n] = flat
    return padded.reshape(nb, block), n


def quantize(arr: np.ndarray, codec: QuantCodec) -> QuantizedTensor:
    """Encode ``arr`` (any shape, float dtype) into flat uint8 + scales."""
    arr = np.asarray(arr)
    shape, dtype = tuple(arr.shape), arr.dtype.str
    if codec.name == "bf16":
        import ml_dtypes

        codes = np.ascontiguousarray(
            arr.astype(ml_dtypes.bfloat16)).reshape(-1).view(np.uint8)
        return QuantizedTensor(codec.name, codec.block, shape, dtype,
                               codes, np.zeros(0, np.float32))
    xb, n = _to_blocks(arr, codec.block)
    xb, amax = _sanitize_blocks(xb)
    if codec.name == "int8":
        scales = np.where(amax > 0, amax / np.float32(127.0),
                          np.float32(1.0)).astype(np.float32)
        q = np.clip(np.rint(xb / scales[:, None]), -127, 127).astype(np.int8)
        codes = q.reshape(-1).view(np.uint8)
    else:  # fp8 (e4m3 emulation)
        import ml_dtypes

        scales = np.where(amax > 0, amax / np.float32(FP8_MAX),
                          np.float32(1.0)).astype(np.float32)
        y = (xb / scales[:, None]).astype(np.float32)
        # e4m3fn overflows to NaN above the finite max: clamp first (the
        # scale maps amax exactly to FP8_MAX, but fp32 division can land
        # one ulp above it)
        y = np.clip(y, -FP8_MAX, FP8_MAX)
        codes = np.ascontiguousarray(
            y.astype(ml_dtypes.float8_e4m3fn)).reshape(-1).view(np.uint8)
    # the ragged tail's block padding never crosses the wire (codes are
    # 1 byte/element, so truncation at n is exact; decode re-pads)
    return QuantizedTensor(codec.name, codec.block, shape, dtype,
                           np.ascontiguousarray(codes[:n]), scales)


def dequantize(qt: QuantizedTensor) -> np.ndarray:
    """Decode back to the original shape/dtype (lossy)."""
    n = int(np.prod(qt.shape, dtype=np.int64)) if qt.shape else 1
    if qt.codec == "bf16":
        import ml_dtypes

        vals = qt.codes.view(ml_dtypes.bfloat16).astype(np.float32)
        return vals[:n].reshape(qt.shape).astype(np.dtype(qt.dtype))
    nb = qt.scales.size
    codes = qt.codes
    if codes.size < nb * qt.block:  # re-pad the truncated ragged tail
        codes = np.concatenate(
            [codes, np.zeros(nb * qt.block - codes.size, np.uint8)])
    if qt.codec == "int8":
        q = codes.view(np.int8).astype(np.float32)
    else:
        import ml_dtypes

        q = codes.view(ml_dtypes.float8_e4m3fn).astype(np.float32)
    vals = (q.reshape(nb, -1) * qt.scales[:, None]).reshape(-1)
    return vals[:n].reshape(qt.shape).astype(np.dtype(qt.dtype))


# -- single-buffer wire form (weight-plane chunks) --------------------------


def encode_array(arr: np.ndarray, codec: QuantCodec
                 ) -> Tuple[np.ndarray, Dict[str, Any]]:
    """Encode into ONE flat uint8 buffer ``[scales fp32 | codes]`` plus a
    JSON-safe meta dict — the weight-store chunk encoding (the manifest
    records ``enc``; pulls decode transparently)."""
    qt = quantize(arr, codec)
    wire = np.empty(qt.scales.nbytes + qt.codes.nbytes, np.uint8)
    wire[:qt.scales.nbytes] = qt.scales.view(np.uint8)
    wire[qt.scales.nbytes:] = qt.codes
    return wire, qt.meta()


def decode_array(wire: np.ndarray, meta: Dict[str, Any]) -> np.ndarray:
    wire = np.asarray(wire, dtype=np.uint8).reshape(-1)
    nscales = int(meta["nscales"])
    scales = wire[:nscales * 4].view(np.float32).copy()
    codes = wire[nscales * 4:].copy()
    return dequantize(QuantizedTensor(
        meta["codec"], int(meta["block"]), tuple(meta["shape"]),
        meta["dtype"], codes, scales))


# -- actor-wire form (collective payloads; arrays ride out-of-band) ---------


def to_wire(qt: QuantizedTensor, extra: Optional[np.ndarray] = None
            ) -> Dict[str, Any]:
    """``extra`` is an optional small fp32 vector (metrics / control
    scalars) that rides the same exchange UNQUANTIZED and is summed
    exactly at the reduce point — one collective round trip instead of
    two, without quantizing the few-float leg (see "when NOT to
    quantize")."""
    d = {"codec": qt.codec, "block": qt.block, "shape": list(qt.shape),
         "dtype": qt.dtype, "codes": qt.codes, "scales": qt.scales}
    if extra is not None:
        d["extra"] = np.asarray(extra, np.float32)
    return d


def from_wire(d: Dict[str, Any]) -> QuantizedTensor:
    return QuantizedTensor(d["codec"], int(d["block"]),
                           tuple(d["shape"]), d["dtype"],
                           np.asarray(d["codes"], np.uint8),
                           np.asarray(d["scales"], np.float32))


def wire_nbytes(d: Dict[str, Any]) -> int:
    return int(np.asarray(d["codes"]).nbytes
               + np.asarray(d["scales"]).nbytes)


# -- error feedback ---------------------------------------------------------


class ErrorFeedback:
    """Per-key residual accumulator: quantization error is carried into
    the next step's contribution instead of lost.

    ``encode(key, arr)`` returns ``quantize(arr + residual[key])`` and
    stores the new residual. Keys are caller-chosen (bucket index, dtype,
    leg) and residuals are local — never synchronized."""

    def __init__(self, codec: QuantCodec):
        self.codec = codec
        self._residual: Dict[Any, np.ndarray] = {}

    def encode(self, key: Any, arr: np.ndarray) -> QuantizedTensor:
        x = np.asarray(arr, np.float32)
        res = self._residual.get(key)
        if res is not None and res.shape == x.shape:
            x = x + res
        qt = quantize(x, self.codec)
        self._residual[key] = (x - dequantize(qt).astype(np.float32)
                               ).reshape(x.shape)
        return qt

    def residual_norm(self, key: Any) -> float:
        res = self._residual.get(key)
        return 0.0 if res is None else float(np.linalg.norm(res))

    def reset(self):
        self._residual.clear()


# -- store-side reduce (dequant-accumulate fp32, requantize once) -----------


def reduce_wire_payloads(payloads, codec_spec: str) -> Dict[str, Any]:
    """The reduce point of the quantized collective: dequantize every
    rank's contribution, accumulate in fp32, and re-quantize ONCE for the
    broadcast leg. Runs inside the CollectiveStore actor (pure numpy)."""
    name, _, block = codec_spec.partition(":")
    codec = QuantCodec(name, int(block) if block else DEFAULT_BLOCK)
    total: Optional[np.ndarray] = None
    extra: Optional[np.ndarray] = None
    for p in payloads:
        val = dequantize(from_wire(p)).astype(np.float32)
        total = val if total is None else total + val
        if p.get("extra") is not None:
            e = np.asarray(p["extra"], np.float32)
            extra = e if extra is None else extra + e
    return to_wire(quantize(total, codec), extra=extra)
