"""Gradient compression for collectives.

Reference parity: `horovod/tensorflow/compression.py` / `horovod/torch/compression.py`
(74 LoC each) — a ``Compressor`` pair (compress/decompress) selected via
``Compression.none`` / ``Compression.fp16``.

TPU-native note: on TPU the natural 16-bit wire format is **bfloat16** (MXU
native, same exponent range as fp32 so no loss-scaling needed); ``fp16`` is
kept for API parity and ``bf16`` added as the recommended choice.

Beyond the reference's dtype casts this module owns the **block-quantized
int8 wire format** (EQuARX-style, PAPERS.md arXiv:2506.17615): per-block
(default 256 elements) symmetric int8 payload with one fp32 scale per
block. Unlike the cast compressors, int8 quantization cannot run at the
framework layer — per-rank scales don't commute with the sum — so
``Compression.int8`` / ``Compression.int8_dcn`` are *wire markers*:
``compress()`` is the identity and the executor lowers the
quantize → allreduce → dequantize pipeline into its single compiled
collective program (`runtime/executor.py`). The numerics live here
(`quantize_blocks` / `dequantize_blocks`, jnp reference implementation
with a Pallas kernel fast path) so tests, error feedback and the executor
share one definition.

Adaptive v2 (this module + `ops/adaptive.py`): ``int4`` halves the packed
wire again (two values per byte, scale = absmax/7), and ``adaptive`` lets a
per-bucket selector pick int4/int8/bf16 from running statistics of the
reduced gradients — the enqueued wire string is ``adaptive:<mode>`` so the
coordinator negotiates the concrete bitwidth before the collective fires.

Job-wide default: ``HOROVOD_COMPRESSION={none,fp16,bf16,int8,int8-dcn,
int4,adaptive}`` (resolved by :func:`from_env`); ``HOROVOD_INT8_BLOCK``
overrides the block size for every block-quantized mode.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

DEFAULT_BLOCK = 256


def block_size() -> int:
    """Quantization block length (``HOROVOD_INT8_BLOCK``, default 256)."""
    b = int(os.environ.get("HOROVOD_INT8_BLOCK", DEFAULT_BLOCK))
    if b <= 0:
        raise ValueError(f"HOROVOD_INT8_BLOCK={b}: must be positive")
    return b


def _kernels():
    from . import pallas_kernels
    return pallas_kernels


def quantize_blocks(x, block: int | None = None, bits: int = 8):
    """Block-quantize a float array to (int8 payload, fp32 scales).

    ``x`` is flattened; its length must be a multiple of ``block`` (callers
    pad — see :func:`quantize_roundtrip` / the executor's chunk padding).
    Returns ``(q, scales)`` with ``q`` int8 of ``x.size`` elements and
    ``scales`` fp32 of ``x.size // block`` elements, where block ``i`` of
    ``x`` is approximately ``q[i*block:(i+1)*block] * scales[i]``.

    ``bits`` picks the quantization grid: 8 (scale = absmax/127, the
    default) or 4 (scale = absmax/7). The 4-bit grid is returned unpacked
    (one int8 per value) — nibble packing is a wire-layout concern and
    lives in ``pallas_kernels.int4_quantize_pack``; this function is the
    numerics shared by error feedback and the tests.
    """
    if bits not in (4, 8):
        raise ValueError(f"quantize_blocks: bits must be 4 or 8, got {bits}")
    block = block or block_size()
    flat = jnp.ravel(x).astype(jnp.float32)
    if flat.shape[0] % block:
        raise ValueError(
            f"quantize_blocks: size {flat.shape[0]} not a multiple of "
            f"block {block}")
    x2 = flat.reshape(-1, block)
    pk = _kernels()
    if bits == 8 and pk.kernel_path("int8_quantize", x2) == "pallas":
        q2, s2 = pk.int8_quantize_2d(x2)
        return q2.reshape(-1), s2[:, 0]
    qmax = 127.0 if bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    scale = absmax * (1.0 / qmax)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q2 = jnp.clip(jnp.round(x2 / safe), -qmax, qmax).astype(jnp.int8)
    return q2.reshape(-1), scale[:, 0]


def dequantize_blocks(q, scales, dtype=jnp.float32, block: int | None = None):
    """Inverse of :func:`quantize_blocks`: int8 payload × per-block scale."""
    block = block or block_size()
    q2 = jnp.ravel(q).reshape(-1, block)
    s2 = jnp.ravel(scales).astype(jnp.float32)[:, None]
    pk = _kernels()
    if pk.kernel_path("int8_dequantize", q2, s2) == "pallas":
        y2 = pk.int8_dequantize_2d(q2, s2)
    else:
        y2 = q2.astype(jnp.float32) * s2
    return y2.reshape(-1).astype(dtype)


def quantize_roundtrip(x, block: int | None = None, bits: int = 8):
    """Quantize→dequantize ``x`` (any shape/float dtype), padding internally.

    This is the exact value the quantized wire delivers for a single-rank
    hop; error feedback (`optim/distributed.py`) uses it to compute the
    residual the wire dropped. ``bits=4`` measures the int4 grid.
    """
    block = block or block_size()
    # metric lives here (the eager entry point), not in the jit-traced
    # quantize/dequantize bodies where an inc would count compiles
    from ..metrics import instruments

    instruments.error_feedback_roundtrips().inc()
    flat = jnp.ravel(x)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, s = quantize_blocks(flat, block, bits=bits)
    y = dequantize_blocks(q, s, dtype=x.dtype, block=block)
    return y[:n].reshape(x.shape)


def wire_footprint(num_elements: int, mode: str,
                   block: int | None = None) -> int:
    """Bytes a fused bucket of ``num_elements`` fp32 elements moves over the
    wire for one reduce-scatter + allgather round in the given mode
    (``int8-dcn`` counts the quantized DCN hop — its ICI hops ride bf16).
    """
    per_elem = {"none": 4, "fp32": 4, "fp16": 2, "bf16": 2}.get(mode)
    if per_elem is not None:
        return 2 * num_elements * per_elem
    if mode in ("int8", "int8-dcn", "int8_dcn"):
        block = block or block_size()
        blocks = -(-num_elements // block)
        return 2 * (num_elements + 4 * blocks)
    if mode == "int4":
        # packed nibbles: half a byte per element plus the same one-f32-
        # per-block scale overhead as int8 (wire rows are
        # [block//2 payload bytes | 4 scale bytes])
        block = block or block_size()
        blocks = -(-num_elements // block)
        return 2 * (-(-num_elements // 2) + 4 * blocks)
    if mode == "adaptive" or mode.startswith("adaptive:"):
        # mixed wire: the footprint is whatever concrete mode the selector
        # negotiated for this bucket ("adaptive:<mode>"); bare "adaptive"
        # counts the int8 startup default
        concrete = mode.split(":", 1)[1] if ":" in mode else "int8"
        return wire_footprint(num_elements, concrete, block)
    raise ValueError(f"unknown compression mode {mode!r}")


def _gspmd_seg_bytes(elems: int, mode: str, block: int | None) -> int:
    """Bytes one exchanged segment of ``elems`` f32 elements costs on a
    GSPMD wire: packed rows for int8/int4, raw elements otherwise."""
    per_elem = {"none": 4, "fp32": 4, "fp16": 2, "bf16": 2}.get(mode)
    if per_elem is not None:
        return elems * per_elem
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown GSPMD wire mode {mode!r}")
    block = block or block_size()
    rows = -(-elems // block)
    row_bytes = (block if mode == "int8" else block // 2) + 4
    return rows * row_bytes


def gspmd_wire_footprint(num_elements: int, mode: str, world: int,
                         block: int | None = None,
                         algorithm: str = "ring",
                         hosts: int | None = None) -> int:
    """Bytes ONE rank puts on the wire for one allreduce on the compiled
    path, per zoo member (`spmd.quantized_allreduce` and friends).

    Quantized modes move packed rows — ``[block payload | 4 scale bytes]``
    for int8, ``[block//2 | 4]`` for int4 — over chunks rounded up to
    whole blocks. ``none``/``fp32`` (``bf16``/``fp16``) count the same
    schedule moving raw 4-byte (2-byte) elements with no scale overhead:
    the exact-wire denominator behind ``hvd_quantization_ratio`` and the
    three-way `scaling_bench`. ``world == 1`` is wireless.

    ``algorithm`` rows (docs/autotune.md):

    * ``ring`` — reduce-scatter + all-gather, each phase ``world - 1``
      hops of one per-rank chunk. The ZeRO-1 variant moves the same
      total. Byte-identical to the pre-zoo catalog.
    * ``tree`` — recursive halving/doubling, ``2 * log2(world)``
      exchanges of a payload half (`spmd.quantized_allreduce_tree`);
      non-power-of-two worlds ride the ring and cost ring bytes.
    * ``hier`` — intra-host reduce-scatter + all-gather over
      ``chips = world // hosts`` plus the cross-host phase on the owned
      chunk (`spmd.quantized_allreduce_hier`); ``hosts`` must be a proper
      divisor of ``world`` or the ring row applies.
    """
    if world <= 1:
        return 0
    if algorithm == "tree" and world & (world - 1) == 0:
        half = -(-num_elements // 2)
        rounds = world.bit_length() - 1
        return 2 * rounds * _gspmd_seg_bytes(half, mode, block)
    if (algorithm == "hier" and hosts and 1 < hosts < world
            and world % hosts == 0):
        chips = world // hosts
        chunk = -(-num_elements // chips)
        sub = -(-chunk // hosts)
        intra = 2 * (chips - 1) * _gspmd_seg_bytes(chunk, mode, block)
        cross = 2 * (hosts - 1) * _gspmd_seg_bytes(sub, mode, block)
        return intra + cross
    return (2 * (world - 1)
            * _gspmd_seg_bytes(-(-num_elements // world), mode, block))


def gspmd_cross_host_footprint(num_elements: int, mode: str, world: int,
                               hosts: int, block: int | None = None,
                               algorithm: str = "ring") -> int:
    """Bytes crossing a host boundary, summed over ALL ranks, for one
    allreduce under a host-major ``(hosts, chips)`` layout — the number
    the hierarchical schedule exists to shrink (`ci/pod_smoke.py`
    ``check_algo_hierarchical``).

    ``ring``: the flat ring has ``hosts`` boundary edges and every edge
    carries ``world - 1`` chunk segments per phase. ``hier``: only the
    phase-2 host-ring rows cross hosts — ``chips`` parallel rings of
    ``hosts`` edges, each edge carrying ``hosts - 1`` sub-chunk segments
    per phase. ``tree``: at recursion distance ``d >= chips`` every rank's
    partner is on another host; smaller distances stay intra-host.
    """
    if world <= 1 or hosts <= 1 or world % hosts:
        return 0
    chips = world // hosts
    if algorithm == "hier":
        chunk = -(-num_elements // chips)
        sub = -(-chunk // hosts)
        return (2 * (hosts - 1) * chips * hosts
                * _gspmd_seg_bytes(sub, mode, block))
    if algorithm == "tree" and world & (world - 1) == 0:
        total = 0
        seg = -(-num_elements // 2)
        d = world >> 1
        while d >= 1:
            if d >= chips:  # partner p ^ d sits on another host
                total += 2 * world * _gspmd_seg_bytes(seg, mode, block)
            seg = -(-seg // 2)
            d >>= 1
        return total
    chunk = -(-num_elements // world)
    return 2 * (world - 1) * hosts * _gspmd_seg_bytes(chunk, mode, block)


def moe_wire_footprint(per_peer_elements: int, mode: str, world: int,
                       block: int | None = None) -> int:
    """Bytes ONE device puts on the wire for one capacity-dispatch MoE
    round (`parallel/expert.py`): the dispatch all_to_all plus the
    combine all_to_all over the ``ep`` axis, each moving ``world - 1``
    remote per-peer payloads of ``per_peer_elements`` f32 elements
    (``E_loc * capacity * d``; the slab a device keeps for its own
    experts never touches the wire).

    Quantized modes move packed rows — ``[block | 4 scale bytes]`` for
    int8, ``[block//2 | 4]`` for int4 — with each peer's payload padded
    to whole blocks independently (`spmd.quantized_all_to_all`).
    ``none``/``fp32`` (``bf16``/``fp16``) count the exact exchange moving
    raw 4-byte (2-byte) elements: ``bf16`` is the denominator behind the
    "dispatch bytes ≤60% of the bf16 exchange" CI bar. ``world == 1``
    is wireless.
    """
    if world <= 1:
        return 0
    per_elem = {"none": 4, "fp32": 4, "fp16": 2, "bf16": 2}.get(mode)
    if per_elem is not None:
        return 2 * (world - 1) * per_peer_elements * per_elem
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown MoE wire mode {mode!r}")
    block = block or block_size()
    rows = -(-per_peer_elements // block)
    row_bytes = (block if mode == "int8" else block // 2) + 4
    return 2 * (world - 1) * rows * row_bytes


class Compressor:
    """Interface: compress before enqueue, decompress after completion.

    ``wire`` names an in-collective wire format the executor should apply
    (None = the wire carries whatever ``compress`` produced).
    """

    wire: str | None = None

    @staticmethod
    def compress(tensor):
        """Returns (compressed_tensor, context_for_decompress)."""
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError

    @classmethod
    def roundtrip(cls, tensor):
        """The value the wire delivers for this compressor (lossy part only;
        used by error feedback to measure what the wire dropped)."""
        comp, ctx = cls.compress(tensor)
        return cls.decompress(comp, ctx)


class NoneCompressor(Compressor):
    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype = None

    @classmethod
    def compress(cls, tensor):
        dtype = tensor.dtype
        if jnp.issubdtype(dtype, jnp.floating):
            return tensor.astype(cls.wire_dtype), dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        return tensor if ctx is None else tensor.astype(ctx)


class FP16Compressor(_CastCompressor):
    wire_dtype = jnp.float16


class BF16Compressor(_CastCompressor):
    wire_dtype = jnp.bfloat16


class _WireCompressor(NoneCompressor):
    """Marker: framework-level identity, executor-level quantized wire.

    The tensor is enqueued unchanged; ``TensorTableEntry.compression``
    carries ``wire`` through negotiation so every rank's executor compiles
    the same quantize → collective → dequantize program. Integer/bool
    tensors and buckets below the executor's size floor bypass quantization
    inside the executor (the entry still negotiates the mode so ranks
    agree on the program).
    """

    #: quantization grid the wire applies (4 or 8)
    bits = 8

    @classmethod
    def roundtrip(cls, tensor):
        if not jnp.issubdtype(jnp.asarray(tensor).dtype, jnp.floating):
            return tensor
        return quantize_roundtrip(tensor, bits=cls.bits)


class Int8Compressor(_WireCompressor):
    wire = "int8"


class Int8DcnCompressor(_WireCompressor):
    """int8 on the slow DCN hop only; ICI hops ride bf16 (EQuARX mixed
    mode applied to the two-level hierarchical allreduce)."""

    wire = "int8-dcn"


class Int4Compressor(_WireCompressor):
    """int4 packed wire: two values per byte, scale = absmax/7 per block.
    Roughly half of int8's bytes; pair with ``error_feedback=True`` — the
    4-bit grid drops enough signal that EF is what keeps convergence at
    parity (the convergence gate in ops/adaptive.py measures exactly
    this)."""

    wire = "int4"
    bits = 4


class AdaptiveCompressor(_WireCompressor):
    """Mixed-bitwidth wire (``HOROVOD_COMPRESSION=adaptive``).

    A per-bucket selector (`ops/adaptive.py`) keeps running statistics of
    the *reduced* gradients — absmax/variance EMAs plus the measured
    quantization-residual norm at each candidate grid — and picks the
    cheapest of int4/int8/bf16 whose error stays under tolerance,
    re-deciding every ``HOROVOD_ADAPTIVE_INTERVAL`` observations. The
    statistics come from the allreduced output, which is identical on
    every rank, so decisions are deterministic and cross-rank consistent;
    the enqueued wire string ``adaptive:<mode>`` is still negotiated
    through the coordinator (Response.compression wins), which resolves
    any transition race to the least aggressive proposal.

    Selector state is class-level (one per process): ranks sharing a
    process observe identical reduced buckets, so sharing is harmless, and
    ``reset()`` gives tests a clean slate.
    """

    wire = "adaptive:int8"  # startup default, before any statistics exist
    _selector = None

    @classmethod
    def selector(cls):
        if cls._selector is None:
            from . import adaptive as _adaptive

            cls._selector = _adaptive.BitwidthSelector()
        return cls._selector

    @classmethod
    def reset(cls):
        cls._selector = None

    @classmethod
    def wire_for(cls, name: str) -> str:
        return "adaptive:" + cls.selector().decide(name)

    @classmethod
    def observe(cls, name: str, flat) -> None:
        cls.selector().observe(name, flat)

    @classmethod
    def roundtrip(cls, tensor):
        # EF residual against the most aggressive grid currently active:
        # one residual tree serves every bucket, so this measures the
        # worst-case wire loss (buckets on a finer grid over-correct
        # slightly, which EF tolerates — the residual shrinks next step)
        if not jnp.issubdtype(jnp.asarray(tensor).dtype, jnp.floating):
            return tensor
        bits = cls.selector().min_active_bits()
        if bits >= 16:
            return tensor.astype(jnp.bfloat16).astype(tensor.dtype)
        return quantize_roundtrip(tensor, bits=bits)


class Compression:
    """Parity with the reference's Compression namespace."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor  # TPU-native extension
    int8 = Int8Compressor  # block-quantized wire (executor-fused)
    int8_dcn = Int8DcnCompressor
    int4 = Int4Compressor  # packed-nibble wire (executor-fused)
    adaptive = AdaptiveCompressor  # per-bucket mixed bitwidth


_BY_NAME = {
    "": NoneCompressor,
    "none": NoneCompressor,
    "fp16": FP16Compressor,
    "bf16": BF16Compressor,
    "int8": Int8Compressor,
    "int8-dcn": Int8DcnCompressor,
    "int8_dcn": Int8DcnCompressor,
    "int4": Int4Compressor,
    "adaptive": AdaptiveCompressor,
}

# wire-name → compressor, for reconstructing the negotiated mode from
# control-plane metadata on ranks that had no local entry.
BY_WIRE = {"int8": Int8Compressor, "int8-dcn": Int8DcnCompressor,
           "int4": Int4Compressor}


def by_name(name: str):
    """Resolve a compression mode name (the HOROVOD_COMPRESSION values)."""
    try:
        return _BY_NAME[str(name).strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown compression {name!r}; expected one of "
            "none/fp16/bf16/int8/int8-dcn/int4/adaptive") from None


def from_env(default=NoneCompressor):
    """Job-wide default compressor from ``HOROVOD_COMPRESSION``."""
    name = os.environ.get("HOROVOD_COMPRESSION")
    return by_name(name) if name else default
