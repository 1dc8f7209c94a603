"""FabricSpec + Fabric: the single typed entry point to the whole IMC stack.

The paper's 8T SRAM macro exposes MAC, logic, and memory modes through ONE
array interface; this module is the software mirror of that device descriptor.
A :class:`FabricSpec` is a frozen, hashable value object that fully determines
how a GEMM (or logic op) executes on the modeled fabric:

  * precision   — ``bits_a`` x ``bits_w`` (asymmetric supported end-to-end)
  * geometry    — ``rows`` x ``cols`` macro tiles
  * fidelity    — ``mode="exact"`` (digital-equivalent int GEMM) or
                  ``mode="sim"`` (offset-binary bit-planes, charge-sharing RBL
                  voltage, comparator thermometer decode)
  * engine      — ``backend="jnp" | "pallas" | "auto"`` (auto picks the fused
                  Pallas kernel on TPU, the plane-batched jnp engine elsewhere)
  * non-ideality— ``noise=NoiseSpec(...)`` (device mismatch on the effective
                  count, comparator offset), PRNG-keyed

Because the spec is hashable it rides ``jax.jit`` as a single static argument:
two calls with equal specs share one compiled executable, and the spec can be
embedded in model configs (:class:`repro.configs.base.ModelConfig.fabric`)
without breaking their hashability.

The :class:`Fabric` facade bundles the four things you do with a macro:

    fab = Fabric(FabricSpec(mode="sim", noise=NoiseSpec(mismatch_sigma=0.05)))
    y   = fab.matmul(x, w, key=key)          # quant -> fabric GEMM -> dequant
    y   = fab.linear(params, x, key=key)     # Linear layer, STE backward
    c   = fab.logic(a, b, "XOR")             # MAC-derived bitwise logic
    rep = fab.cost(x.shape, w.shape)         # energy/latency FabricReport

Backend resolution happens in a small registry keyed by
``(mode, backend, noisy)``; unsupported combinations raise immediately at
spec/facade construction instead of silently falling back.  Noisy sims are
first-class on BOTH engines: the jnp keyed path is the statistical oracle,
and ``backend="pallas"`` runs the whole noisy pyramid as one fused kernel
with in-kernel PRNG (``kernels/bitplane_mac``) — same key -> identical
outputs, cross-engine agreement pinned on moments/quantiles (different PRNG
streams make bit-identity impossible).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import constants as C
from repro.core.bitserial import bitserial_matmul_unsigned, decode_group_counts
from repro.core.energy import FabricReport, fabric_matmul_cost
from repro.core.logic import (OPS, add_nbit, logic_from_count, logic_word)
from repro.core.quant import quantize, signed_product_correction, to_offset_binary

MODES = ("exact", "sim")
BACKENDS = ("auto", "jnp", "pallas")


# ------------------------------------------------------------------- specs
@dataclass(frozen=True)
class NoiseSpec:
    """Analog non-idealities of the sim path (both optional, PRNG-keyed).

    mismatch_sigma          — voltage-referred device mismatch on the
                              effective MAC count (stddev per unit sqrt(count);
                              the paper-calibrated value is
                              ``constants.MC_SIGMA_VK``).
    comparator_offset_sigma — input-referred comparator offset (V) on the
                              thermometer decode references.
    """

    mismatch_sigma: Optional[float] = None
    comparator_offset_sigma: Optional[float] = None

    def __post_init__(self):
        for name in ("mismatch_sigma", "comparator_offset_sigma"):
            v = getattr(self, name)
            if v is not None and v < 0:
                raise ValueError(f"NoiseSpec.{name} must be >= 0, got {v}")

    @property
    def enabled(self) -> bool:
        return (self.mismatch_sigma is not None
                or self.comparator_offset_sigma is not None)

    @classmethod
    def calibrated(cls) -> "NoiseSpec":
        """Device mismatch at the paper-calibrated sigma (Fig 6 / §IV-C)."""
        return cls(mismatch_sigma=C.MC_SIGMA_VK)


@dataclass(frozen=True)
class FabricSpec:
    """Complete, hashable description of one IMC fabric configuration."""

    bits_a: int = 8
    bits_w: int = 8
    rows: int = C.ROWS
    cols: int = C.COLS
    mode: str = "exact"  # exact | sim
    backend: str = "auto"  # auto | jnp | pallas
    noise: Optional[NoiseSpec] = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}")
        for name in ("bits_a", "bits_w"):
            b = getattr(self, name)
            if not 2 <= b <= 8:
                raise ValueError(f"{name} must be in [2, 8] (int8 storage), "
                                 f"got {b}")
        if self.rows < 2 or self.cols < 1:
            raise ValueError(f"invalid geometry {self.rows}x{self.cols}")
        # Canonicalize an all-off NoiseSpec to None so equality/hashing (and
        # hence the jit cache) don't distinguish "no noise" spellings.
        if self.noise is not None and not self.noise.enabled:
            object.__setattr__(self, "noise", None)
        if self.noisy and self.mode != "sim":
            raise ValueError(
                "noise is only meaningful on the analog sim path; use "
                "mode='sim' (exact mode is the noise-free digital equivalent)")

    # -------------------------------------------------------------- derived
    @property
    def noisy(self) -> bool:
        return self.noise is not None

    @property
    def bits(self) -> int:
        """Symmetric precision accessor; raises when bits_a != bits_w."""
        if self.bits_a != self.bits_w:
            raise ValueError(
                f"spec has asymmetric precision {self.bits_a}x{self.bits_w}; "
                "use bits_a/bits_w explicitly")
        return self.bits_a

    def resolve_backend(self) -> str:
        """Concrete engine name: 'auto' -> pallas on TPU, jnp elsewhere."""
        if self.backend != "auto":
            return self.backend
        if jax.default_backend() == "tpu":
            return "pallas"
        return "jnp"

    @property
    def label(self) -> str:
        """Short row label for benches/logs: e.g. ``sim/jnp+noise``."""
        s = f"{self.mode}/{self.resolve_backend()}"
        return s + "+noise" if self.noisy else s

    def replace(self, **kw) -> "FabricSpec":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------- registry
# (mode, backend, noisy) -> engine(qa, qw, spec, key) -> int32 accumulator
# qa: int[..., K] signed quantized activations; qw: int[K, N] signed weights.
_ENGINES: Dict[Tuple[str, str, bool], Callable] = {}


def register_engine(mode: str, backend: str, noisy: bool):
    def deco(fn):
        _ENGINES[(mode, backend, noisy)] = fn
        return fn
    return deco


def resolve_engine(spec: FabricSpec) -> Callable:
    """Engine for a spec; raises (early, with the menu) on unsupported combos."""
    key = (spec.mode, spec.resolve_backend(), spec.noisy)
    try:
        return _ENGINES[key]
    except KeyError:
        combos = ", ".join(
            f"{m}/{b}{'+noise' if n else ''}" for m, b, n in sorted(_ENGINES))
        raise ValueError(
            f"no fabric engine for mode={key[0]!r} backend={key[1]!r} "
            f"noisy={key[2]}; supported: {combos}") from None


def int_matmul(qa, qw):
    """int8 x int8 -> int32 matmul (MXU-native on TPU)."""
    return jax.lax.dot_general(
        qa.astype(jnp.int8), qw.astype(jnp.int8),
        (((qa.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@register_engine("exact", "jnp", False)
def _exact_jnp(qa, qw, spec, key):
    return int_matmul(qa, qw)


@register_engine("exact", "pallas", False)
def _exact_pallas(qa, qw, spec, key):
    from repro.kernels.imc_mac.ops import imc_mac

    return imc_mac(qa, qw)


def _sim_correction(qa, qw, spec):
    with jax.named_scope("fabric.quantize"):
        u_a = to_offset_binary(qa, spec.bits_a)
        u_w = to_offset_binary(qw, spec.bits_w)
        return u_a, u_w, signed_product_correction(u_a, u_w, spec.bits_a,
                                                   spec.bits_w)


@register_engine("sim", "jnp", False)
def _sim_jnp(qa, qw, spec, key):
    u_a, u_w, corr = _sim_correction(qa, qw, spec)
    uu = bitserial_matmul_unsigned(u_a, u_w, bits_a=spec.bits_a,
                                   bits_w=spec.bits_w, rows=spec.rows,
                                   mode="sim")
    return uu - corr


@register_engine("sim", "jnp", True)
def _sim_jnp_noisy(qa, qw, spec, key):
    u_a, u_w, corr = _sim_correction(qa, qw, spec)
    uu = bitserial_matmul_unsigned(
        u_a, u_w, bits_a=spec.bits_a, bits_w=spec.bits_w, rows=spec.rows,
        mode="sim", key=key, mismatch_sigma=spec.noise.mismatch_sigma,
        comparator_offset_sigma=spec.noise.comparator_offset_sigma)
    return uu - corr


@register_engine("sim", "pallas", False)
def _sim_pallas(qa, qw, spec, key):
    from repro.kernels.bitplane_mac.ops import bitplane_mac

    u_a, u_w, corr = _sim_correction(qa, qw, spec)
    uu = bitplane_mac(u_a, u_w, bits_a=spec.bits_a, bits_w=spec.bits_w,
                      rows=spec.rows)
    return uu - corr


@register_engine("sim", "pallas", True)
def _sim_pallas_noisy(qa, qw, spec, key):
    from repro.kernels.bitplane_mac.ops import bitplane_mac_noisy

    u_a, u_w, corr = _sim_correction(qa, qw, spec)
    uu = bitplane_mac_noisy(
        u_a, u_w, key, bits_a=spec.bits_a, bits_w=spec.bits_w,
        rows=spec.rows, mismatch_sigma=spec.noise.mismatch_sigma,
        comparator_offset_sigma=spec.noise.comparator_offset_sigma)
    return uu - corr


# ------------------------------------------------------------------ matmul
@partial(jax.jit, static_argnames=("spec", "geom"))
def _fabric_matmul_jit(x, w, spec: FabricSpec, key, geom):
    del geom  # cache-key only: retrace when tuned kernel geometry changes
    engine = resolve_engine(spec)
    with jax.named_scope("fabric.quantize"):
        qx = quantize(x, spec.bits_a, axis=None)
        qw = quantize(w, spec.bits_w, axis=0)  # per-column (output channel)
    acc = engine(qx.q, qw.q, spec, key)
    return acc.astype(jnp.float32) * qx.scale * qw.scale.reshape(
        (1,) * (acc.ndim - 1) + (-1,))


def fabric_matmul(x, w, spec: FabricSpec = FabricSpec(), *, key=None):
    """y[..., N] ~= x[..., K] @ w[K, N] through the fabric described by spec.

    Activations quantize per-tensor (dynamic) at ``bits_a``; weights per
    output channel at ``bits_w``.  ``key`` is required iff ``spec.noisy``.

    Plain wrapper over one inner jit whose static arguments are the spec and
    the autotuner's :func:`~repro.kernels.autotune.geometry_token` — equal
    specs under an unchanged tuning state share one compiled executable, and
    a re-tune (or a ``REPRO_TUNE_*`` pin change) busts the cache instead of
    silently reusing stale tile geometry.

    The stages run under named scopes, so a profiler trace names the device
    ops of each: ``fabric.quantize`` (the codes, and on the sim path their
    offset binary and the signed-product correction), then ``fabric.planes``
    and ``fabric.mac`` (the ``bitplane_mac`` engine's bit-plane explode and
    padding, and its kernel).  The rescale takes no scope: XLA fuses it into
    the op that reads the projection.
    """
    from repro.kernels import autotune

    if spec.noisy and key is None:
        raise ValueError(f"spec {spec.label} is noisy: pass key=")
    return _fabric_matmul_jit(x, w, spec, key, autotune.geometry_token())


# the recompile-detector tests watch the inner jit's cache through the wrapper
fabric_matmul._cache_size = _fabric_matmul_jit._cache_size


# ------------------------------------------------------------------ facade
class Fabric:
    """All four faces of the macro — GEMM, layer, logic, cost — on one spec."""

    def __init__(self, spec: FabricSpec = FabricSpec()):
        self.spec = spec
        self._engine = resolve_engine(spec)  # raise on bad combos up front

    def __repr__(self):
        return f"Fabric({self.spec!r})"

    def matmul(self, x, w, *, key=None):
        """Quantize -> fabric GEMM -> dequant.  See :func:`fabric_matmul`."""
        return fabric_matmul(x, w, self.spec, key=key)

    def linear(self, params, x, *, key=None):
        """Linear layer on the fabric: params {"w": (K,N)[, "b": (N,)]}.

        Straight-through estimator backward (gradients of the float matmul),
        so the same layer trains and serves.
        """
        from repro.core.imc_linear import imc_linear_apply

        return imc_linear_apply(x, params["w"], params.get("b"),
                                spec=self.spec, key=key)

    def _count_decode(self, key):
        """counts -> counts through the spec's decode path, fresh-keyed.

        Each call of the returned closure folds a new stream off ``key``, so
        multi-evaluation word ops (ripple-carry stages) draw independent
        noise per MAC activation — mirroring distinct array cycles.
        """
        if self.spec.noisy and key is None:
            raise ValueError(f"spec {self.spec.label} is noisy: pass key=")
        state = {"n": 0}

        def decode(count):
            kw = {}
            if self.spec.noisy:
                kw = dict(key=jax.random.fold_in(key, state["n"]),
                          mismatch_sigma=self.spec.noise.mismatch_sigma,
                          comparator_offset_sigma=(
                              self.spec.noise.comparator_offset_sigma))
                state["n"] += 1
            return decode_group_counts(count, mode=self.spec.mode,
                                       rows=self.spec.rows, **kw)

        return decode

    def logic(self, a, b, op: str, *, key=None):
        """MAC-derived bitwise logic (paper §III-B..E, Table II).

        ``a``, ``b``: {0,1} arrays (any shape, broadcastable).  The 2-operand
        MAC count goes through the spec's decode path (exact clip, or the
        analog voltage + comparator model for ``mode="sim"``, with the spec's
        noise when keyed), then the Boolean function is read off the count.
        """
        op = op.upper()
        if op not in OPS:
            raise ValueError(f"op must be one of {OPS}, got {op!r}")
        count = jnp.asarray(a, jnp.int32) + jnp.asarray(b, jnp.int32)
        dec = self._count_decode(key)(count)
        return logic_from_count(dec, m=2)[op]

    def logic_word(self, a, b, op: str, *, bits: int = 8, key=None):
        """Bitwise ``op`` over packed ``bits``-wide words (paper §III).

        8 columns evaluate in parallel per macro activation, so a uint8 word
        is one MAC cycle; every column's count runs through the spec's
        decode path (``key`` required iff noisy).
        """
        return logic_word(a, b, op, bits=bits, decode=self._count_decode(key))

    def add_nbit(self, a, b, *, bits: int = 8, key=None):
        """Ripple-carry word addition from 1-bit MAC adders (paper §III-E).

        Returns ``(sum mod 2**bits, carry_out)``; each half-adder stage is a
        separate keyed MAC evaluation under a noisy spec.
        """
        return add_nbit(a, b, bits=bits, decode=self._count_decode(key))

    def cost(self, x_shape, w_shape, *, n_macros: int = 1,
             schedule: str = "weight_stationary") -> FabricReport:
        """Energy/latency projection of ``matmul(x, w)`` on this fabric."""
        *batch, k = x_shape
        m = 1
        for b in batch:
            m *= b
        return fabric_matmul_cost(m, k, w_shape[-1], bits_a=self.spec.bits_a,
                                  bits_w=self.spec.bits_w, rows=self.spec.rows,
                                  cols=self.spec.cols, n_macros=n_macros,
                                  schedule=schedule)


# --------------------------------------------------------------------- CLI
def add_fabric_cli(ap) -> None:
    """Attach the FabricSpec flags to an argparse parser (launchers' edge)."""
    ap.add_argument("--imc", "--imc-mode", dest="imc", default=None,
                    choices=("off",) + MODES,
                    help="route every projection through the IMC fabric")
    ap.add_argument("--imc-bits", type=int, default=8,
                    help="activation precision (bits_a)")
    ap.add_argument("--imc-bits-w", type=int, default=0,
                    help="weight precision (0 -> same as --imc-bits)")
    ap.add_argument("--imc-backend", default="auto", choices=BACKENDS)
    ap.add_argument("--imc-mismatch-sigma", "--imc-noise-sigma",
                    dest="imc_mismatch_sigma", type=float, default=None,
                    help="device mismatch sigma (sim only; keyed per step)")
    ap.add_argument("--imc-comparator-sigma", type=float, default=None,
                    help="comparator offset sigma in V (sim only; keyed)")


def fabric_from_cli(args) -> Optional[FabricSpec]:
    """FabricSpec from the add_fabric_cli flags; None when --imc is off/unset."""
    if args.imc in (None, "off"):
        return None
    noise = None
    if args.imc_mismatch_sigma is not None or args.imc_comparator_sigma is not None:
        noise = NoiseSpec(mismatch_sigma=args.imc_mismatch_sigma,
                          comparator_offset_sigma=args.imc_comparator_sigma)
    return FabricSpec(bits_a=args.imc_bits,
                      bits_w=args.imc_bits_w or args.imc_bits,
                      mode=args.imc, backend=args.imc_backend, noise=noise)


def apply_fabric_cli(ap, args, cfg, *, jitted_what: str = "launcher"):
    """Shared launcher edge: fold the --imc* flags into a ModelConfig.

    Returns ``cfg`` unchanged when ``--imc`` wasn't given.  Noisy specs are
    first-class here: the launch Engine threads a per-step PRNG key through
    every jitted step, so ``--imc-noise-sigma`` runs at training/serving
    scale (seed-reproducible via the Engine's ``noise_seed``).
    """
    if args.imc is None:
        return cfg
    spec = fabric_from_cli(args)
    # spec built at the edge; imc_mode="off" clears the legacy channel so
    # the typed field (or None, for --imc off) is the one source of truth
    return dataclasses.replace(cfg, fabric=spec, imc_mode="off")
