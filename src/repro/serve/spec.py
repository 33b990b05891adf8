"""Declarative readout specs: *what to read*, not *which method to call*.

The paper's core claim is that one in-sensor substrate (the eDRAM SAE)
serves many downstream consumers — exponential time-surfaces for
classification, STCF masks for denoising, and the Sec. II-B comparison
representations (event-count, EBBI, SRAM-quantized TS).  A
``ReadoutSpec`` is a static, hashable description of the *products* one
read returns; the serving engine compiles **one fused batched dispatch
per unique spec** and caches it exactly like the ``backend`` selector —
the spec is part of the jit cache key, so reading the same spec twice
never retraces, and every product in a composed spec comes out of the
same compiled program over the same slot-pool state snapshot.  Specs
are **pool-size-agnostic**: nothing here mentions the slot count, so an
elastic engine growing or shrinking its padded slot axis retraces a
spec at most once per capacity *bucket* (array shapes key the jit
cache; revisited buckets hit their cached entries) and hot-path reads
at a stable capacity never recompile.

Specs form a **two-stage product graph**.  Stage-0 *surface products*
read off the pool state (each a frozen, hashable descriptor; construct
via the helpers)::

    surface(...)       decayed time surface (the classic TS readout)
    mask(...)          comparator mask V > V_tw (denoiser front end)
    stcf(...)          dense STCF patch-support map
    count(n_bits)      saturating per-pixel event counter  [refs 32, 33]
    ebbi()             event-based binary image            [refs 34, 35]
    sae_raw()          raw last-timestamp surface (-inf = never) [21, 36]
    ts_quantized(...)  TS from n_T-bit wrapping timestamps  [ref 26]

Stage-1 *head products* consume stage-0 products **by name** inside the
same fused dispatch — the spec serves answers, not just arrays::

    classify(inputs, weights, ...)   CNN class logits over a stack of
                                     surface products (the paper's
                                     GoogLeNet-on-TS task, Sec. IV-D)
    denoise(input, threshold)        STCF-thresholded event-label map
                                     (the paper's denoise verdicts)

Compose them by name — one call, one dispatch, surfaces and answers::

    spec = ReadoutSpec(surface=surface(), stcf=stcf(),
                       logits=classify(inputs=("surface",)),
                       labels=denoise(input="stcf"))
    out = session.read(spec, t_now)   # {"surface":..., "logits":...}

A head's inputs must name stage-0 products *of the right family* in the
same spec (``classify`` eats ``surface()`` products, ``denoise`` eats a
``stcf()``); the constructor validates the wiring, so a malformed graph
never reaches tracing.  ``compile_spec`` plans a spec into its staged
form (stage-0 sub-spec + head list + static thresholds); the engine
compiles **one fused batched dispatch per unique spec** with the heads
inlined behind an ``optimization_barrier`` over their inputs.

``count`` is the only product needing extra device state (a per-slot
counter plane); the engine materializes it only when its config declares
a spec that asks for it (``TSEngineConfig.specs``).  Everything else
reads off the SAE the pool already carries.  ``classify`` weights are
resolved by *static key* (``serve.heads``: registry / checkpoint
directory / deterministic default) and enter the fused program as traced
arguments, never baked constants.

Bit-identity contract: the ``surface()`` product of *any* spec is
bit-identical to a standalone ``kernels.ops.ts_decay`` dispatch on the
same state — products are independent subgraphs sharing only the SAE
input, so composing them cannot re-contract the decay math (gated by
``tests/test_kernel_equivalence.py::check_spec_read_bitwise`` and the
engine differential suite).  Heads extend the contract: every head
input passes through an ``optimization_barrier`` before the head
consumes it, so (a) adding a head to a spec cannot re-contract the
stage-0 math it reads, and (b) the fused in-dispatch head output is
bitwise the standalone head applied to the served stage-0 products
(gated by ``check_spec_head_bitwise`` and the stream-oracle tests).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import edram
from repro.core import representations as representations_mod
from repro.core import stcf as stcf_mod
from repro.kernels import ops
from repro.serve import fidelity as fidelity_mod
from repro.serve.fidelity import FidelityModel

__all__ = [
    "ReadoutSpec", "Surface", "Mask", "Stcf", "Count", "Ebbi", "SaeRaw",
    "TsQuantized", "Classify", "Denoise", "surface", "mask", "stcf",
    "count", "ebbi", "sae_raw", "ts_quantized", "classify", "denoise",
    "SURFACE_SPEC", "needs_counts", "CompiledSpec", "compile_spec",
    "read_compiled", "read_stage0", "apply_heads", "read_products",
]


# ----------------------------------------------------------------------------
# product descriptors (frozen -> hashable -> usable as static jit args)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Surface:
    """Decayed time surface.  ``mode``/``tau``/``cmem_f`` default to the
    engine config's decay (None = inherit), so ``surface()`` is exactly
    the pre-spec ``readout``; overriding them serves a second decay
    profile off the same SAE without touching the engine config.

    ``fidelity`` attaches an analog read model (``serve.fidelity``):
    the same fused dispatch then serves what the eDRAM silicon would
    have read — leakage transient + per-cell spread (+ half-select for
    ``analog_2d``).  ``None``/``IDEAL`` is the digital read; analog
    modes require the product to resolve to ``mode="edram"``."""

    mode: Optional[str] = None       # "edram" | "ideal" | None (engine's)
    tau: Optional[float] = None      # ideal-TS decay constant override
    cmem_f: Optional[float] = None   # eDRAM storage-cap override
    fidelity: Optional[FidelityModel] = None

    def __post_init__(self):
        assert self.mode in (None, "edram", "ideal"), self.mode
        if self.fidelity is not None and not isinstance(
            self.fidelity, FidelityModel
        ):
            raise TypeError(
                f"Surface fidelity must be a FidelityModel, "
                f"got {self.fidelity!r}"
            )


@dataclasses.dataclass(frozen=True)
class Mask:
    """Comparator mask V > V_tw (the STCF window test, one bool plane).
    ``tau_tw`` overrides the engine's correlation window."""

    tau_tw: Optional[float] = None
    decay: Surface = Surface()


@dataclasses.dataclass(frozen=True)
class Stcf:
    """Dense STCF patch-support map (int32 per pixel): SAE -> decay ->
    comparator -> patch sum, fused in one kernel pass."""

    radius: Optional[int] = None     # None = engine's stcf_radius
    tau_tw: Optional[float] = None   # None = engine's correlation window
    include_self: bool = False
    decay: Surface = Surface()

    @classmethod
    def from_config(cls, cfg: stcf_mod.STCFConfig) -> "Stcf":
        return cls(radius=cfg.radius, tau_tw=cfg.tau_tw,
                   include_self=cfg.include_self)


@dataclasses.dataclass(frozen=True)
class Count:
    """Saturating n-bit per-pixel event counter (float32 in [0, 2^n-1]),
    polarity-merged like the offline ``representations.event_count``.
    Needs the engine's counter plane (``TSEngineConfig.specs``)."""

    n_bits: int = 4


@dataclasses.dataclass(frozen=True)
class Ebbi:
    """Event-based binary image: 1.0 where any event landed since the
    slot was attached (polarity-merged, like ``representations.ebbi``)."""


@dataclasses.dataclass(frozen=True)
class SaeRaw:
    """The raw surface of active events: last write time per cell in
    seconds, -inf = never written."""


@dataclasses.dataclass(frozen=True)
class TsQuantized:
    """TS rebuilt from n_T-bit, ``tick``-second timestamps that WRAP on
    overflow — the SRAM TPI failure mode of ref [26].  ``tau`` defaults
    to the engine's ideal-TS constant."""

    n_bits: int = 16
    tick: float = 1e-3
    tau: Optional[float] = None


_STAGE0_TYPES = (Surface, Mask, Stcf, Count, Ebbi, SaeRaw, TsQuantized)


# ----------------------------------------------------------------------------
# stage-1 head products: consume stage-0 products by name, serve answers
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Classify:
    """CNN class logits over a stack of surface products (stage-1 head).

    ``inputs`` names ``Surface`` products of the same spec, stacked into
    the channel axis (``models.frontends.ts_stack_frontend``) and fed to
    ``models.cnn.cnn_apply`` — K inputs with different decay profiles
    form the multi-timescale representation.  ``weights`` is a *static*
    key resolved to a param pytree by ``serve.heads`` (registry /
    checkpoint directory / deterministic ``"default"``); the params ride
    the fused dispatch as traced arguments.
    """

    inputs: Tuple[str, ...] = ("surface",)
    weights: str = "default"
    n_classes: int = 10
    width: int = 32

    def __post_init__(self):
        if isinstance(self.inputs, str):
            raise TypeError(
                f"Classify inputs must be a tuple of product names, got "
                f"the bare string {self.inputs!r} (write "
                f"inputs=({self.inputs!r},))"
            )
        object.__setattr__(self, "inputs", tuple(self.inputs))
        if not self.inputs:
            raise ValueError("Classify needs at least one input product")


@dataclasses.dataclass(frozen=True)
class Denoise:
    """STCF-thresholded event-label map (stage-1 head): True where the
    named ``Stcf`` product's patch support reaches ``threshold``
    (``None`` = the engine's ``stcf_threshold``) — the paper's denoise
    verdict as a servable per-pixel bool plane."""

    input: str = "stcf"
    threshold: Optional[int] = None


_HEAD_TYPES = (Classify, Denoise)
_PRODUCT_TYPES = _STAGE0_TYPES + _HEAD_TYPES

#: which stage-0 family each head's inputs must come from
_HEAD_INPUT_TYPES = {Classify: Surface, Denoise: Stcf}

# lowercase helpers: the constructor surface users actually type
surface = Surface
mask = Mask
stcf = Stcf
count = Count
ebbi = Ebbi
sae_raw = SaeRaw
ts_quantized = TsQuantized
classify = Classify
denoise = Denoise


def _validate_ranges(name: str, p) -> None:
    """Range-check the static knobs of one product at spec construction
    (named ``ValueError`` here instead of an opaque trace error deep in
    ``read_stage0``).  Bounds: counter reads and quantized stamps pass
    through exact float32 integer arithmetic, which holds up to 2^24."""
    if isinstance(p, Count):
        if not isinstance(p.n_bits, int) or not 1 <= p.n_bits <= 24:
            raise ValueError(
                f"product {name!r}: Count.n_bits must be an int in "
                f"[1, 24], got {p.n_bits!r}"
            )
    elif isinstance(p, TsQuantized):
        if not isinstance(p.n_bits, int) or not 1 <= p.n_bits <= 24:
            raise ValueError(
                f"product {name!r}: TsQuantized.n_bits must be an int "
                f"in [1, 24], got {p.n_bits!r}"
            )
        if not (np.isfinite(p.tick) and p.tick > 0.0):
            raise ValueError(
                f"product {name!r}: TsQuantized.tick must be a finite "
                f"positive duration in seconds, got {p.tick!r}"
            )


# ----------------------------------------------------------------------------
# the spec
# ----------------------------------------------------------------------------

class ReadoutSpec:
    """An immutable, hashable composition of named readout products.

    Construct with keyword arguments mapping output names to product
    descriptors::

        ReadoutSpec(surface=surface(), stcf=stcf(), count=count(4))

    The name is the key the read result carries the product under; any
    identifier works (``ReadoutSpec(fast=surface(tau=0.01))``).  Two
    specs with the same (name, product) pairs are equal and hash equal
    regardless of construction order, so they share one compiled
    program — the spec is the jit cache key, like ``backend``.
    """

    __slots__ = ("products", "_hash")

    def __init__(self, **products):
        if not products:
            raise ValueError("a ReadoutSpec needs at least one product")
        for name, p in products.items():
            if not isinstance(p, _PRODUCT_TYPES):
                raise TypeError(
                    f"product {name!r} must be one of "
                    f"{[t.__name__ for t in _PRODUCT_TYPES]}, got {p!r}"
                )
        for name, p in products.items():   # range checks: fail here, with
            _validate_ranges(name, p)      # the product name, not in jit
        for name, p in products.items():   # head wiring: validated here,
            if not isinstance(p, _HEAD_TYPES):   # before any tracing
                continue
            want = _HEAD_INPUT_TYPES[type(p)]
            inputs = p.inputs if isinstance(p, Classify) else (p.input,)
            for inp in inputs:
                got = products.get(inp)
                if got is None:
                    raise ValueError(
                        f"head {name!r} consumes product {inp!r}, which "
                        f"this spec does not define"
                    )
                if not isinstance(got, want):
                    raise ValueError(
                        f"head {name!r} needs a {want.__name__} product "
                        f"for input {inp!r}, got "
                        f"{type(got).__name__} (heads cannot consume "
                        "other heads)"
                    )
        object.__setattr__(self, "products",
                           tuple(sorted(products.items())))
        object.__setattr__(self, "_hash", hash(self.products))

    def __setattr__(self, *_):
        raise AttributeError("ReadoutSpec is immutable")

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (isinstance(other, ReadoutSpec)
                and self.products == other.products)

    def __repr__(self):
        inner = ", ".join(f"{n}={p!r}" for n, p in self.products)
        return f"ReadoutSpec({inner})"

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.products)

    def __contains__(self, name: str) -> bool:
        return any(n == name for n, _ in self.products)

    def __getitem__(self, name: str):
        for n, p in self.products:
            if n == name:
                return p
        raise KeyError(name)

    def surface_products(self) -> Tuple[Tuple[str, Surface], ...]:
        return tuple((n, p) for n, p in self.products
                     if isinstance(p, Surface))

    def head_products(self) -> Tuple[Tuple[str, object], ...]:
        """The (name, head) pairs of this spec's stage-1 products."""
        return tuple((n, p) for n, p in self.products
                     if isinstance(p, _HEAD_TYPES))

    @property
    def has_heads(self) -> bool:
        return any(isinstance(p, _HEAD_TYPES) for _, p in self.products)

    def stage0(self) -> "ReadoutSpec":
        """The stage-0 sub-spec: this spec minus its heads.  Equal specs
        share equal stage-0 sub-specs, which is what lets ``read_many``
        batch head-bearing tiers onto one surface dispatch."""
        s0 = {n: p for n, p in self.products
              if not isinstance(p, _HEAD_TYPES)}
        return self if len(s0) == len(self.products) else ReadoutSpec(**s0)


#: the spec behind the classic ``readout``: one decayed surface, engine decay
SURFACE_SPEC = ReadoutSpec(surface=Surface())


def needs_counts(spec: ReadoutSpec) -> bool:
    """Whether serving ``spec`` requires the pool's counter plane:
    ``count`` products read it directly, and ``analog_2d``-fidelity
    products need it for their half-select row/column hit counts."""
    return (any(isinstance(p, Count) for _, p in spec.products)
            or fidelity_mod.spec_needs_hits(spec))


# ----------------------------------------------------------------------------
# spec resolution: static descriptors -> traced decay params
# ----------------------------------------------------------------------------

def _decay_params(p: Surface, cfg) -> edram.DecayParams:
    """Decay params for one surface-like product under engine config
    ``cfg`` (a ``TSEngineConfig``); every ``None`` field inherits.

    Fails fast on overrides the resolved mode cannot use: a ``tau`` on
    an eDRAM read (or ``cmem_f`` on an ideal one) would otherwise be
    silently ignored and serve the engine-default surface.
    """
    mode = p.mode or cfg.mode
    if mode == "ideal":
        if p.fidelity is not None and p.fidelity.is_analog:
            raise ValueError(
                f"surface product resolves to mode='ideal' but carries "
                f"analog fidelity {p.fidelity.mode!r}; the analog models "
                "emulate the eDRAM cell (pass mode='edram' or drop the "
                "fidelity)"
            )
        if p.cmem_f is not None:
            raise ValueError(
                f"surface product resolves to mode='ideal' but sets "
                f"cmem_f={p.cmem_f}; cmem_f only shapes the eDRAM "
                "transient (pass mode='edram' or drop it)"
            )
        return representations_mod.edram_ideal_params(
            p.tau if p.tau is not None else cfg.tau
        )
    if p.tau is not None:
        raise ValueError(
            f"surface product resolves to mode='edram' but sets "
            f"tau={p.tau}; tau only shapes the ideal exponential "
            "(pass mode='ideal' or drop it)"
        )
    return edram.decay_params_for_cmem(
        p.cmem_f if p.cmem_f is not None else cfg.cmem_f
    )


def _v_tw(decay: Surface, tau_tw: Optional[float], cfg) -> float:
    """Static comparator threshold for a window product (host float —
    part of the jit cache key, matching ``ops``' static ``v_tw``)."""
    tw = tau_tw if tau_tw is not None else cfg.tau_tw
    mode = decay.mode or cfg.mode
    if mode == "ideal":
        tau = decay.tau if decay.tau is not None else cfg.tau
        return float(np.exp(-tw / tau))
    return float(edram.v_tw_for_window(tw, _decay_params(decay, cfg)))


def resolve_static(spec: ReadoutSpec, cfg) -> Tuple[Tuple[str, float], ...]:
    """Per-product *static* comparator thresholds for ``spec`` under
    ``cfg``: a hashable ``(name, v_tw)`` tuple that travels with the spec
    into the jit cache key (``kernels.ops`` takes ``v_tw`` static, so it
    must be a host float resolved before tracing)."""
    return tuple(
        (name, _v_tw(p.decay, p.tau_tw, cfg))
        for name, p in spec.products if isinstance(p, (Mask, Stcf))
    )


def resolve_dynamic(spec: ReadoutSpec, cfg) -> Dict[str, edram.DecayParams]:
    """Per-product *traced* decay params for ``spec`` under ``cfg``.

    Keeping params runtime arguments (not trace-time constants) is what
    preserves bit-identity with the unsharded/pre-spec paths — baking
    them in would let XLA constant-fold the transcendentals differently
    (same rule the sharded engine follows)."""
    dyn: Dict[str, edram.DecayParams] = {}
    for name, p in spec.products:
        if isinstance(p, Surface):
            dyn[name] = _decay_params(p, cfg)
        elif isinstance(p, (Mask, Stcf)):
            dyn[name] = _decay_params(p.decay, cfg)
        elif isinstance(p, TsQuantized):
            dyn[name] = representations_mod.edram_ideal_params(
                p.tau if p.tau is not None else cfg.tau
            )
    return dyn


# ----------------------------------------------------------------------------
# compile pass: one spec -> its staged plan
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompiledSpec:
    """The staged plan of one spec under one engine config (hashable —
    every field is static — so it can travel inside jit cache keys).

    ``stage0`` is the spec minus its heads (``spec`` itself when there
    are none — heads-free specs plan to themselves, value-identically to
    the flat system this replaced); ``heads`` lists the stage-1 products
    in canonical (sorted-name) order; ``statics`` carries the host-
    resolved comparator thresholds of the stage-0 window products.
    """

    spec: ReadoutSpec
    stage0: ReadoutSpec
    heads: Tuple[Tuple[str, object], ...]
    statics: Tuple[Tuple[str, float], ...]

    @property
    def has_heads(self) -> bool:
        return bool(self.heads)


def compile_spec(spec: ReadoutSpec, cfg) -> CompiledSpec:
    """Plan ``spec`` as a two-stage product graph under engine config
    ``cfg``: split stage-0 products from heads and resolve the static
    thresholds.  Head input wiring was validated at spec construction;
    this pass is where per-config resolution (thresholds; later,
    anything shape-dependent) happens.  Equal (spec, cfg) pairs compile
    to equal plans, preserving the spec-is-the-jit-cache-key property.
    """
    return CompiledSpec(
        spec=spec,
        stage0=spec.stage0(),
        heads=spec.head_products(),
        statics=resolve_static(spec, cfg),
    )


def _analog_read(
    sae, counts, t_now, params, fid, noise_step, generation, name, cfg,
    backend,
):
    """One analog surface read inside the fused stage-0 program: draw
    the per-cell spread from the (seed, step, slot-epoch) key contract,
    pull half-select hit counts off the counter plane for ``analog_2d``,
    and dispatch ``ops.ts_analog_read``.  sigma = 0 skips the draw, so
    that path IS the digital ``ts_decay`` program (the bitwise anchor).
    """
    eps = None
    if fidelity_mod.needs_noise(fid):
        if noise_step is None or generation is None:
            raise ValueError(
                f"spec product {name!r} draws per-cell noise; the read "
                "must thread noise_step and the slot generations "
                "(engine.read(..., noise_step=...))"
            )
        eps = fidelity_mod.cell_eps(fid, noise_step, generation,
                                    sae.shape[1:])
    row_hits = col_hits = None
    if fid.mode == "analog_2d":
        if counts is None:
            raise ValueError(
                f"spec product {name!r} has analog_2d fidelity and "
                "needs the counter plane for its half-select hit "
                "counts; declare the spec in TSEngineConfig.specs"
            )
        row_hits, col_hits = fidelity_mod.crossbar_hits(counts)
    return ops.ts_analog_read(
        sae, t_now, params, eps=eps, row_hits=row_hits, col_hits=col_hits,
        alpha=fid.alpha, coupling=fid.coupling, block=cfg.block,
        backend=backend,
    )


def read_stage0(
    sae: jax.Array,                        # (S, P, H, W) slot-pool SAE
    counts,                                # (S, H, W) int32 or None
    t_now,
    dynamic: Dict[str, edram.DecayParams],  # traced, from resolve_dynamic
    spec: ReadoutSpec,                     # static — stage-0 products only
    cfg,                                   # static (TSEngineConfig)
    backend: str,                          # static, pre-resolved
    statics: Tuple[Tuple[str, float], ...] = (),  # from resolve_static
    noise_step=None,                       # traced int — runtime step index
    generation=None,                       # (S,) int32 — slot attach epochs
) -> Dict[str, jax.Array]:
    """Trace-time body of the stage-0 pass: every surface product from
    one program.

    Called under jit (single-device) or shard_map (device-parallel) with
    ``spec``/``cfg``/``backend``/``statics`` static.  Each product
    dispatches the same ``kernels.ops`` entry its standalone method used
    — independent subgraphs over the shared SAE input, so within-product
    math (and bits) match the unfused dispatches.  ``noise_step`` /
    ``generation`` feed the analog-fidelity noise keys and are only
    required when a product actually draws noise (a spec either needs
    them or not — statically — so the pytree structure per spec is
    stable and existing call sites pass nothing).
    """
    v_tws = dict(statics)
    out: Dict[str, jax.Array] = {}
    for name, p in spec.products:
        fid = fidelity_mod.product_fidelity(p)
        analog = fid is not None and fid.is_analog
        if isinstance(p, Surface):
            if analog:
                out[name] = _analog_read(
                    sae, counts, t_now, dynamic[name], fid, noise_step,
                    generation, name, cfg, backend,
                )
            else:
                out[name] = ops.ts_decay(sae, t_now, dynamic[name],
                                         block=cfg.block, backend=backend)
        elif isinstance(p, Mask):
            if analog:
                v = _analog_read(
                    sae, counts, t_now, dynamic[name], fid, noise_step,
                    generation, name, cfg, backend,
                )
                out[name] = v > v_tws[name]
            else:
                _, m = ops.ts_decay_with_mask(
                    sae, t_now, dynamic[name], v_tw_static=v_tws[name],
                    block=cfg.block, backend=backend,
                )
                out[name] = m
        elif isinstance(p, Stcf):
            radius = p.radius if p.radius is not None else cfg.stcf_radius
            if analog:
                v = _analog_read(
                    sae, counts, t_now, dynamic[name], fid, noise_step,
                    generation, name, cfg, backend,
                )
                out[name] = ops.stcf_support(
                    v > v_tws[name], radius=radius,
                    include_self=p.include_self, backend=backend,
                )
            else:
                out[name] = ops.stcf_support_fused(
                    sae, dynamic[name], v_tws[name], t_now,
                    radius=radius, include_self=p.include_self,
                    backend=backend,
                )
        elif isinstance(p, Count):
            if counts is None:
                raise ValueError(
                    f"spec product {name!r} needs the counter plane; "
                    "declare a count-bearing spec in TSEngineConfig.specs"
                )
            out[name] = ops.event_count_read(counts, n_bits=p.n_bits)
        elif isinstance(p, Ebbi):
            out[name] = ops.ebbi_read(sae)
        elif isinstance(p, SaeRaw):
            out[name] = sae
        elif isinstance(p, TsQuantized):
            stored = ops.ts_quantize_sae(sae, n_bits=p.n_bits, tick=p.tick)
            out[name] = ops.ts_wrapped_read(
                stored, t_now, dynamic[name], n_bits=p.n_bits, tick=p.tick,
                block=cfg.block, backend=backend,
            )
        else:  # pragma: no cover — closed by the constructor type check
            raise TypeError(p)
    return out


def apply_heads(
    stage0_out: Dict[str, jax.Array],      # the served stage-0 products
    head_params,                           # {head name: params} or None
    compiled: CompiledSpec,                # static plan
    cfg,                                   # static (TSEngineConfig)
) -> Dict[str, jax.Array]:
    """Trace-time body of the stage-1 pass: every head off the served
    stage-0 products.

    Each head input crosses an ``optimization_barrier`` first, which is
    what makes the staged contract hold *by construction*: XLA cannot
    fuse a head into the stage-0 subgraph it reads (so stage-0 bits
    match a heads-free read of the same products), and the head subgraph
    consumes exactly the barriered values (so the fused in-dispatch
    output is bitwise the standalone head applied to the read arrays —
    the same program, traced from the same jaxpr).  Shard-safe: logits
    and label maps lead with the slot axis and every op is per-slot, so
    the sharded plan runs this body shard-locally with zero collectives.
    """
    head_params = head_params or {}
    out: Dict[str, jax.Array] = {}
    for name, h in compiled.heads:
        if isinstance(h, Classify):
            from repro.models import cnn
            from repro.models.frontends import ts_stack_frontend

            stack = ts_stack_frontend(
                [jax.lax.optimization_barrier(stage0_out[n])
                 for n in h.inputs]
            )
            out[name] = cnn.cnn_apply(head_params[name], stack)
        elif isinstance(h, Denoise):
            thr = (h.threshold if h.threshold is not None
                   else cfg.stcf_threshold)
            sup = jax.lax.optimization_barrier(stage0_out[h.input])
            out[name] = sup >= thr
        else:  # pragma: no cover — closed by the constructor type check
            raise TypeError(h)
    return out


def read_compiled(
    sae: jax.Array,
    counts,
    t_now,
    dynamic: Dict[str, edram.DecayParams],
    compiled: CompiledSpec,                # static plan from compile_spec
    cfg,
    backend: str,
    head_params=None,                      # {head name: params}, traced
    noise_step=None,                       # traced int (analog fidelity)
    generation=None,                       # (S,) int32 slot epochs
) -> Dict[str, jax.Array]:
    """Trace-time body of one staged spec read: stage-0 products, then
    heads over them, all in one program, returned in the spec's
    canonical name order."""
    out = read_stage0(sae, counts, t_now, dynamic, compiled.stage0, cfg,
                      backend, compiled.statics, noise_step=noise_step,
                      generation=generation)
    if compiled.heads:
        out.update(apply_heads(out, head_params, compiled, cfg))
    return {name: out[name] for name in compiled.spec.names}


_read_products_warned = False


def read_products(
    sae: jax.Array,
    counts,
    t_now,
    dynamic: Dict[str, edram.DecayParams],
    spec: ReadoutSpec,
    cfg,
    backend: str,
    statics: Tuple[Tuple[str, float], ...] = (),
    head_params=None,
) -> Dict[str, jax.Array]:
    """Deprecated flat-spec entry (one release of grace): use
    ``compile_spec`` + ``read_compiled``.

    Value-identical to the staged path — it *is* the staged path, called
    through a plan compiled on the spot (``statics`` is accepted for the
    old signature's sake and must match ``resolve_static``'s output when
    given).  Warns once per process.
    """
    global _read_products_warned
    if not _read_products_warned:
        _read_products_warned = True
        import warnings

        warnings.warn(
            "serve.spec.read_products() is deprecated; plan the spec "
            "with compile_spec(spec, cfg) and call read_compiled()",
            DeprecationWarning, stacklevel=2,
        )
    # plan built from the given statics (not compile_spec) so the shim
    # stays traceable exactly where the old flat body was
    compiled = CompiledSpec(spec=spec, stage0=spec.stage0(),
                            heads=spec.head_products(),
                            statics=tuple(statics))
    return read_compiled(sae, counts, t_now, dynamic, compiled, cfg,
                         backend, head_params)
