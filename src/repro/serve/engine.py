"""Serving engines.

:class:`Engine` — the continuous-batching engine (the default):

  * slot-based scheduling: a request is admitted the moment a slot frees,
    including mid-decode (``SlotScheduler``);
  * chunked prefill: prompts are processed in fixed-width right-padded
    chunks of ``prefill_chunk`` tokens, one chunk interleaved with each
    decode step, so a long prompt never stalls running requests;
  * block/paged KV cache: attention KV lives in fixed-size pages with
    per-slot page tables (``PagedKVCache``), so cache memory scales with
    live tokens rather than ``batch_size × max_seq``;
  * per-slot decode positions: one jitted ``decode_paged`` step advances
    every active slot at its own sequence length;
  * optional tensor parallelism: ``Engine(mesh=...)`` shards params and
    the paged KV pool over the mesh's ``model`` axis and compiles the
    paged steps with explicit in/out shardings (data parallelism is
    replica-level — :class:`repro.serve.router.ReplicaRouter`).

:class:`BatchToCompletionEngine` — the legacy fixed-batch engine, kept as
the measurable baseline for ``benchmarks/serve_bench.py``: requests are
grouped into fixed batches, prefilled together and decoded until the
*longest* request finishes (head-of-line blocking), with a dense
``(batch, max_seq)`` cache.

Padding conventions (see docs/serving.md):

  * Continuous engine: prompts are RIGHT-padded per chunk. Pad positions
    sit causally in the future of every real token, their KV is scattered
    to the trash page, and decode masks cache rows ``>= pos`` — padded
    positions are therefore never attended.
  * Batch engine: prompts are LEFT-padded (right-aligned) so the batch
    decodes from a uniform position. Pad rows occupy cache rows
    ``[0, pad_len)`` and are attention-masked via ``pad_lens``
    (historically they were NOT masked — fixed here). RoPE is relative,
    so the uniform per-row shift of absolute positions is harmless.

The paper's technique enters through ``qc``: with ``mode="lut_infer"``
every projection runs assignment + LUT lookups (the fused CCM→IMM kernel
path) instead of dense GEMMs — precomputed tables must already be in
``params`` (see ``repro.core.precompute_model``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_codebook import KVCodebook
from repro.core.lut import DENSE, QuantConfig, missing_luts
from repro.obs import Obs, safe_ratio

from .kv_cache import PagedKVCache, PagePoolExhausted
from .scheduler import FinishReason, Request, SlotPhase, SlotScheduler
from .speculative import SpecConfig, accept_tokens

log = logging.getLogger(__name__)

# Degradation ladder (docs/robustness.md): each mode sheds work the
# engine can live without, in order of how cheap the capability is to
# lose. Pressure is pool occupancy (PagedKVCache.pressure).
MODE_NORMAL = 0          # full speculative lookahead, full prefill budget
MODE_NO_SPEC = 1         # speculative lookahead off (spec pages freed)
MODE_SHRINK_PREFILL = 2  # prefill chunk budget cut (decode keeps priority)
MODE_STOP_ADMIT = 3      # no new admissions until pressure clears
MODE_NAMES = ("normal", "no_spec", "shrink_prefill", "stop_admit")


@dataclasses.dataclass(frozen=True)
class DegradationPolicy:
    """Pressure thresholds for the engine's degradation ladder.

    Escalation is immediate: the mode jumps to however many thresholds
    the current pressure crosses. De-escalation is hysteretic: a mode is
    only re-enabled once pressure drops ``hysteresis`` BELOW the
    threshold that disabled it, so the engine cannot flap between modes
    at a threshold boundary. ``mode_for`` is monotone in pressure for a
    fixed current mode (property-tested in tests/test_faults.py).

    Attributes:
      spec_off: at/above this pressure, speculative lookahead is disabled
        (draft pages are pure opportunism — first thing to go).
      chunk_shrink: at/above, the prefill chunk budget is divided by
        ``chunk_divisor`` (floor ``min_chunk``) — decode drains pages,
        prefill only adds them.
      admit_stop: at/above, admission stops entirely (waiting requests
        stay queued; the bounded queue sheds overflow by priority).
      hysteresis: re-enable margin below each threshold.
    """
    spec_off: float = 0.80
    chunk_shrink: float = 0.90
    admit_stop: float = 0.97
    hysteresis: float = 0.10
    chunk_divisor: int = 4
    min_chunk: int = 2

    def __post_init__(self):
        t = (self.spec_off, self.chunk_shrink, self.admit_stop)
        if not (0.0 < t[0] <= t[1] <= t[2] <= 1.0):
            raise ValueError(f"thresholds must satisfy 0 < spec_off <= "
                             f"chunk_shrink <= admit_stop <= 1, got {t}")

    def mode_for(self, pressure: float, current: int) -> int:
        """Next degradation mode given the pool pressure and the mode the
        engine is currently in (hysteresis needs the history)."""
        thresholds = (self.spec_off, self.chunk_shrink, self.admit_stop)
        up = sum(pressure >= t for t in thresholds)
        down = sum(pressure > t - self.hysteresis for t in thresholds)
        return max(up, min(current, down))


#: Default degradation ladder. Hoisted to a module constant (the
#: dataclass is frozen, so sharing one instance across engines is safe)
#: rather than constructed in the signature — a call in a default arg
#: trips the mutable-default lint and hides construction cost at import.
DEFAULT_DEGRADATION = DegradationPolicy()


def _i32(x) -> jax.Array:
    return jnp.asarray(x, jnp.int32)


def _observe_request(obs: Obs, req) -> None:
    """Record one finished request into ``obs.metrics`` (idempotent).

    Emits the ``req.finish.*`` tally and the latency families — step
    clock (TTFT / end-to-end in engine steps, from the scheduler's
    ``arrival`` / ``first_token_step`` / ``finish_step`` stamps) and
    wall clock (``*_s`` histograms plus TPOT, from the ``*_ts``
    ``perf_counter`` stamps) — then closes the request's trace span.
    Both engines and the shed/expire/truncate finish paths funnel here,
    so ``serve_demo`` and ``serve_bench`` report from one accounting.
    """
    if req.finish_reason is None or getattr(req, "_obs_done", False):
        return
    req._obs_done = True
    m = obs.metrics
    m.counter("req.finish." + req.finish_reason.name.lower(),
              unit="requests").inc()
    if req.arrival is not None:
        if req.first_token_step is not None:
            m.histogram("req.ttft_steps", unit="steps", lo=1.0,
                        hi=1e6).observe(req.first_token_step - req.arrival)
        if req.finish_step is not None:
            m.histogram("req.latency_steps", unit="steps", lo=1.0,
                        hi=1e6).observe(req.finish_step - req.arrival)
    if req.arrival_ts is not None:
        if req.first_token_ts is not None:
            m.histogram("req.ttft_s", unit="s").observe(
                req.first_token_ts - req.arrival_ts)
        if req.finish_ts is not None:
            m.histogram("req.latency_s", unit="s").observe(
                req.finish_ts - req.arrival_ts)
            n_decoded = len(req.out_tokens) - 1
            if n_decoded > 0 and req.first_token_ts is not None:
                m.histogram("req.tpot_s", unit="s").observe(
                    (req.finish_ts - req.first_token_ts) / n_decoded)
    tr = obs.tracer
    if tr.enabled and getattr(req, "_obs_traced", False):
        tr.request_end(req._seq, f"req {req._seq}",
                       {"reason": req.finish_reason.name,
                        "tokens": len(req.out_tokens)})


def _with_argmax(logits: jax.Array, kv):
    """Verify-step output shaping: (logits, per-row argmax ids, kv).

    The argmax is computed ON DEVICE so all-greedy speculative rounds
    transfer only (num_slots, k+1) token ids to the host — the full
    logits tensor is fetched lazily, and only when a temperature slot
    needs the distributions for rejection sampling."""
    return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), kv


def _sample_tokens(key: jax.Array, logits: jax.Array,
                   temps: Optional[jax.Array], slot_ids: Sequence[int]):
    """Shared sampling helper. Returns (new_key, tokens (B,) int32).

    Greedy where temperature <= 0, categorical over ``logits / T``
    elsewhere. temps: (B,) fp32, or None when the whole batch is greedy
    (no PRNG state is consumed then).

    Per-slot PRNG: one subkey per call, folded with each row's *slot
    index*, then one independent categorical draw per row — so identical
    requests occupying different slots draw different samples (regression
    test: test_serve_paged.py::test_identical_hot_requests_diverge).
    Sample streams are NOT reproducible across batch compositions: how
    often the key advances depends on engine scheduling.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temps is None:
        return key, greedy
    key, sub = jax.random.split(key)
    keys = jax.vmap(lambda i: jax.random.fold_in(sub, i))(_i32(list(slot_ids)))
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return key, jnp.where(temps > 0.0, sampled.astype(jnp.int32), greedy)


class Engine:
    """Continuous-batching engine over a paged KV cache.

    Args:
      model: ``repro.models.model.Model`` (token-prompt families:
        dense / moe / ssm / hybrid; ``head_layout="heads"``).
      params: model params pytree (LUT tables precomputed for
        ``qc.mode == "lut_infer"``).
      qc: quantisation operating point threaded through every projection.
      batch_size: number of slots (max concurrently running requests).
      max_seq: per-slot sequence capacity (rounded up to a page multiple).
      eos_id: optional stop token.
      seed: PRNG seed for sampling.
      page_size: tokens per KV page.
      num_pages: physical page-pool size; default ``slots ×
        pages_per_slot`` (no oversubscription). Smaller pools admit fewer
        concurrent tokens and may trigger preemption.
      prefill_chunk: static prefill chunk width (must divide max_seq).
      prefix_cache: automatic prefix caching (default on). Fully written
        prompt pages are content-hash indexed as prefill covers them; a
        later request sharing a page-aligned prompt prefix maps those
        pages read-shared and starts prefill at its first uncached token
        (docs/serving.md §Prefix caching). Greedy output is unchanged —
        shared pages hold exactly the KV a cold prefill would recompute.
        Mamba2/hybrid state is not paged, so those families always serve
        cold (the knob is inert there).
      spec_decode: optional :class:`~repro.serve.speculative.SpecConfig`
        enabling self-speculative decoding (docs/speculative.md): a cheap
        drafter (the target's own weights through a low-bit LUT operating
        point, an early-exit prefix, or host-side n-gram lookup) proposes
        up to ``k`` tokens per decoding slot and ONE batched
        ``verify_paged`` call scores them, emitting between 1 and ``k+1``
        tokens per round. Greedy output stays token-identical to
        non-speculative decoding; temperature mode applies rejection
        sampling with the residual correction. Attention (paged KV)
        families only — recurrent state cannot roll back.
      max_queue: optional bound on the waiting queue. A ``submit`` that
        would overflow it sheds the lowest-priority (newest) request
        with a clean ``finish_reason = LoadShedded`` result instead of
        raising — admission control for burst traffic
        (docs/robustness.md). ``None`` = unbounded.
      degradation: :class:`DegradationPolicy` stepping the engine down
        a ladder of modes as pool pressure rises — speculative
        lookahead off, then a shrunken prefill budget, then an admission
        stop — and back up (with hysteresis) as pressure clears. Pass
        ``None`` to disable (the pre-fault-tolerance behaviour).
      mesh: optional ``jax.sharding.Mesh`` (``launch.mesh``) with a
        ``model`` axis. When given, the engine serves TENSOR-PARALLEL over
        the mesh: params are placed by ``parallel.sharding.param_pspecs``
        (codebooks replicated for column-parallel projections,
        subspace-sharded for row-parallel ones), the paged KV pool by
        ``paged_cache_pspecs`` (pages replicated over ``data``,
        kv-heads / head-dim over ``model``), and the jitted
        prefill/decode steps carry explicit in/out shardings so GSPMD
        inserts the row-parallel all-reduce after each subspace-sharded
        LUT accumulate. Data parallelism is replica-level — see
        :class:`repro.serve.router.ReplicaRouter`.
    """

    def __init__(self, model, params, qc: QuantConfig = DENSE,
                 batch_size: int = 8, max_seq: int = 512,
                 eos_id: Optional[int] = None, seed: int = 0,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: int = 32, mesh=None,
                 prefix_cache: bool = True,
                 spec_decode: Optional[SpecConfig] = None,
                 max_queue: Optional[int] = None,
                 degradation: Optional[DegradationPolicy]
                 = DEFAULT_DEGRADATION,
                 kv_codebook: Optional[KVCodebook] = None,
                 obs: Optional[Obs] = None):
        if qc.mode == "lut_infer":
            missing = missing_luts(params)
            if missing:
                # lut_linear_apply would rebuild each table on every call
                raise ValueError(
                    f"lut_infer serving needs precomputed tables "
                    f"(repro.core.precompute_model); {len(missing)} "
                    f"layer(s) have none, e.g. {missing[0]}")
        self.model = model
        self.params = params
        self.qc = qc
        self.num_slots = batch_size
        max_seq = -(-max_seq // page_size) * page_size   # round up to pages
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        self.prefill_chunk = max(2, min(prefill_chunk, max_seq))
        if max_seq % self.prefill_chunk:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must divide "
                f"max_seq ({max_seq})")
        # KV-cache quantization (docs/serving.md §KV-cache quantization):
        # with qc.kv_quant == "vq" the page pool stores uint8 codebook
        # indices; the codebook is fit here, once, from a deterministic
        # calibration prefill (bit-identical across replicas/restarts, so
        # prefix pages hash compatibly) unless the caller supplies one.
        self.kv_codebook = kv_codebook
        if qc.kv_quant == "vq":
            from repro.models.model import ATTN_FAMILIES
            if model.cfg.family not in ATTN_FAMILIES:
                raise ValueError(
                    "kv_quant='vq' quantizes paged attention KV pages; "
                    f"the {model.cfg.family!r} family has recurrent "
                    "state, which has no page rows to encode")
            if self.kv_codebook is None:
                self.kv_codebook = self._fit_kv_codebook()
        elif kv_codebook is not None:
            raise ValueError(
                "kv_codebook supplied but qc.kv_quant is 'none' — set "
                "qc = qc.replace(kv_quant='vq') to serve quantized")
        # Observability bundle (docs/observability.md): every counter
        # below lives in ``obs.metrics`` behind same-named read-only
        # properties, so the attribute surface tests and the router read
        # is unchanged. The registry is always live (counters double as
        # engine state); ``Obs.disabled()`` only compiles out the timing
        # layer (phase histograms + trace spans). The scheduler and KV
        # pool record into the same bundle; a shared ``Tracer`` across
        # replicas merges them into one Perfetto timeline.
        self.obs = obs if obs is not None else Obs()
        met = self.obs.metrics
        self.kv = PagedKVCache(model, self.num_slots, max_seq,
                               page_size=page_size, num_pages=num_pages,
                               prefix_cache=prefix_cache,
                               codebook=self.kv_codebook)
        self.kv.obs = self.obs
        self.scheduler = SlotScheduler(self.num_slots, max_queue=max_queue,
                                       obs=self.obs)
        self.step_count = 0
        # Degradation ladder state (docs/robustness.md): mode 0..3, step
        # counts per mode for the stats surface, and a monotone count of
        # emitted tokens — the router watchdog's progress marker.
        self.degradation = degradation
        self.mode = MODE_NORMAL
        self._c_mode = tuple(
            met.counter(f"engine.mode_steps.{MODE_NAMES[i]}", unit="steps",
                        desc="steps spent in this degradation mode")
            for i in range(4))
        self._c_transitions = met.counter(
            "engine.degradation.transitions", unit="transitions")
        self._c_emitted = met.counter("engine.emitted_tokens",
                                      unit="tokens")
        # Prefix-cache accounting (docs/serving.md §Prefix caching):
        #   prompt_tokens     — prompt tokens admitted (incl. re-admissions)
        #   cached_tokens     — of those, served from shared pages
        #   prefilled_tokens  — tokens actually pushed through prefill
        self._c_prompt = met.counter("engine.prompt_tokens", unit="tokens")
        self._c_cached = met.counter("engine.cached_tokens", unit="tokens")
        self._c_prefilled = met.counter("engine.prefilled_tokens",
                                        unit="tokens")
        self._g_pool_bytes = met.gauge("engine.pool.live_bytes", unit="B")
        self._g_pressure = met.gauge("engine.pool.pressure")
        self._g_mode = met.gauge("engine.mode")

        # Per-slot temperatures live in a DEVICE-RESIDENT (num_slots,)
        # buffer refreshed only when slot occupancy changes (admission /
        # eviction / preemption) — never per decode step. ``temps_uploads``
        # counts the host->device transfers for the regression test.
        self._temps_h = np.zeros((self.num_slots,), np.float32)
        self._temps_dev: Optional[jax.Array] = None
        self._c_temps = met.counter("engine.temps_uploads", unit="uploads")

        self.mesh = mesh
        self._table_sharding = None
        if mesh is None:
            self._jit_prefill = jax.jit(
                lambda p, t, kv, pt, slot, pos, valid: model.prefill_paged(
                    p, t, kv, pt, slot, pos, valid, qc),
                donate_argnums=(2,))
            self._jit_decode = jax.jit(
                lambda p, t, kv, pt, positions: model.decode_paged(
                    p, t, kv, pt, positions, qc),
                donate_argnums=(2,))
            self._jit_verify = jax.jit(
                lambda p, t, kv, pt, pos, nl: _with_argmax(
                    *model.verify_paged(p, t, kv, pt, pos, nl, qc)),
                donate_argnums=(2,))
        else:
            self._init_sharded(mesh)

        # Batch sampling runs JITTED so a steady-state decode step is
        # exactly two compiled calls (decode + sample) and ONE host
        # transfer — the (num_slots,) token vector through _device_read.
        # slot_ids is the full lane range, closed over as a static
        # constant; greedy (temps=None) and temperature batches are two
        # shape classes of the same jit.
        nslots = self.num_slots
        self._jit_sample = jax.jit(
            lambda key, logits, temps: _sample_tokens(
                key, logits, temps, range(nslots)))
        # Host-transfer accounting: every per-step device->host read in
        # the serving loop goes through _device_read, which bumps this.
        self._c_device_reads = met.counter("engine.device_reads",
                                           unit="reads")

        # Speculative decoding (docs/speculative.md): draft cheap, verify
        # with the target in one multi-token call, roll back rejections.
        self.spec = spec_decode
        self.drafter = None
        self._c_spec_rounds = met.counter(      # verify calls issued
            "engine.spec.rounds", unit="rounds")
        self._c_spec_drafted = met.counter(     # proposals scored
            "engine.spec.drafted", unit="tokens")
        self._c_spec_accepted = met.counter(    # proposals that survived
            "engine.spec.accepted", unit="tokens")
        self._c_spec_emitted = met.counter(     # tokens emitted by spec
            "engine.spec.emitted", unit="tokens")
        if spec_decode is not None:
            if not self.kv.paged:
                raise ValueError(
                    "spec_decode needs rewindable paged KV state; the "
                    f"{model.cfg.family!r} family's recurrent state cannot "
                    "roll back rejected draft tokens")
            if spec_decode.k < 1:
                raise ValueError(f"spec_decode.k must be >= 1, got "
                                 f"{spec_decode.k}")
            self._spec_rng = np.random.default_rng(seed)
            self.drafter = spec_decode.build_drafter()
            self.drafter.bind(self)

    def _fit_kv_codebook(self) -> KVCodebook:
        """Fit the KV codebook from a deterministic calibration prefill.

        A fixed token ramp (no PRNG-dependent data) runs through the fp
        dense-cache prefill; the per-layer K/V rows it leaves behind are
        the k-means sample. Everything downstream of (model, params, qc)
        is deterministic — a warm replica and a cold restart fit the same
        codebook, so their quantized prefix pages share one salt space.
        """
        model, cfg = self.model, self.model.cfg
        t = min(128, self.max_seq)
        tokens = (jnp.arange(t, dtype=jnp.int32) * 31 + 7) % cfg.vocab_size
        cache = model.init_cache(1, t)
        _, cache = jax.jit(
            lambda p, b, c: model.prefill(p, b, c, self.qc)
        )(self.params, {"tokens": tokens[None]}, cache)
        rows_k = cache["layers"]["k"][:, 0]            # (L, T, KVH, HD)
        rows_v = cache["layers"]["v"][:, 0]
        return KVCodebook.fit(rows_k, rows_v, v=self.qc.kv_v,
                              c=self.qc.kv_c, key=jax.random.PRNGKey(0))

    def _init_sharded(self, mesh) -> None:
        """Place params + paged cache on ``mesh`` and compile the paged
        entry points with explicit in/out shardings (tensor parallelism
        over the ``model`` axis; see docs/serving.md §Sharded serving)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import (logical_to_sharding,
                                             paged_cache_pspecs,
                                             param_pspecs)
        model, qc, cfg = self.model, self.qc, self.model.cfg
        msize = mesh.shape["model"]
        pshard = logical_to_sharding(
            param_pspecs(self.params, cfg, model_axis_size=msize), mesh)
        self.params = jax.device_put(self.params, pshard)
        cshard = logical_to_sharding(
            paged_cache_pspecs(cfg, mesh,
                               quantized=self.kv_codebook is not None),
            mesh)
        self.kv.data = jax.device_put(self.kv.data, cshard)
        repl = NamedSharding(mesh, P())
        self._table_sharding = repl
        # NOTE: jax.jit is lazy — tracing happens at the first CALL, which
        # the step methods wrap in _mesh_scope() (the ambient mesh the
        # in-model with_sharding_constraint hooks need); scoping the jit
        # construction here would be inert.
        self._jit_prefill = jax.jit(
            lambda p, t, kv, pt, slot, pos, valid: model.prefill_paged(
                p, t, kv, pt, slot, pos, valid, qc, act_sharding=repl),
            in_shardings=(pshard, repl, cshard, repl, repl, repl, repl),
            out_shardings=(repl, cshard),
            donate_argnums=(2,))
        self._jit_decode = jax.jit(
            lambda p, t, kv, pt, positions: model.decode_paged(
                p, t, kv, pt, positions, qc, act_sharding=repl),
            in_shardings=(pshard, repl, cshard, repl, repl),
            out_shardings=(repl, cshard),
            donate_argnums=(2,))
        self._param_sharding = pshard
        self._cache_sharding = cshard
        self._jit_verify = jax.jit(
            lambda p, t, kv, pt, pos, nl: _with_argmax(
                *model.verify_paged(p, t, kv, pt, pos, nl, qc,
                                    act_sharding=repl)),
            in_shardings=(pshard, repl, cshard, repl, repl, repl),
            out_shardings=(repl, repl, cshard),
            donate_argnums=(2,))

    def _mesh_scope(self):
        """Ambient-mesh context for tracing/compiling the jitted steps
        (lets in-model ``with_sharding_constraint`` hooks see the mesh)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    # ------------------------------------------------------------------
    # host transfers
    # ------------------------------------------------------------------
    def _device_read(self, tree):
        """THE device->host funnel for the step loop.

        Every per-step read crosses here as one ``jax.device_get`` of a
        small pytree (token ids, argmax ids, optionally logits) instead
        of scattered ``.item()`` / ``np.asarray`` syncs — so a decode
        step costs exactly one transfer and ``device_reads`` counts them
        for the regression tests (test_recompile_guard.py). This is the
        sanctioned sync point; the `analysis` linter flags any other
        read reachable from the step loop. Because the step loop blocks
        HERE (and only here), the ``device_read`` phase span measures
        the true device wait, not dispatch overhead."""
        self._c_device_reads.inc()
        with self.obs.phase("device_read"):
            return jax.device_get(tree)  # analysis: ok(step-sync)

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _sample(self, logits: jax.Array, temps: Optional[jax.Array],
                slot_ids: Sequence[int]) -> jax.Array:
        """One token per row via :func:`_sample_tokens` (per-slot keys)."""
        self.key, toks = _sample_tokens(self.key, logits, temps, slot_ids)
        return toks

    # ------------------------------------------------------------------
    # per-slot temperature buffer (device-resident)
    # ------------------------------------------------------------------
    def _set_slot_temp(self, slot_idx: int, temp: float) -> None:
        """Update one lane's temperature; invalidates the device buffer
        only when the value actually changes."""
        if self._temps_h[slot_idx] != temp:
            self._temps_h[slot_idx] = temp
            self._temps_dev = None

    def _decode_temps(self) -> Optional[jax.Array]:
        """(num_slots,) device temps, or None when every lane is greedy.

        The device buffer is cached between decode steps and re-uploaded
        only after an occupancy change touched a temperature — the per-step
        host->device churn the batch engine never had is not re-introduced
        here (regression: test_serve_paged.py::
        test_no_per_step_temperature_upload)."""
        if not (self._temps_h > 0.0).any():
            return None
        if self._temps_dev is None:
            if self._table_sharding is not None:
                self._temps_dev = jax.device_put(self._temps_h,
                                                 self._table_sharding)
            else:
                self._temps_dev = jnp.asarray(self._temps_h)
            self._c_temps.inc()
        return self._temps_dev

    @property
    def load(self) -> int:
        """Requests queued or occupying a slot (router dispatch metric)."""
        return len(self.scheduler.waiting) + sum(
            not s.free for s in self.scheduler.slots)

    # ------------------------------------------------------------------
    # registry-backed counter surface (legacy attribute names)
    # ------------------------------------------------------------------
    @property
    def mode_steps(self) -> Dict[int, int]:
        """Steps spent in each degradation mode (``{mode: steps}``)."""
        return {i: c.value for i, c in enumerate(self._c_mode)}

    @property
    def emitted_tokens(self) -> int:
        return self._c_emitted.value

    @property
    def prompt_tokens(self) -> int:
        return self._c_prompt.value

    @property
    def cached_tokens(self) -> int:
        return self._c_cached.value

    @property
    def prefilled_tokens(self) -> int:
        return self._c_prefilled.value

    @property
    def temps_uploads(self) -> int:
        return self._c_temps.value

    @property
    def device_reads(self) -> int:
        return self._c_device_reads.value

    @property
    def spec_rounds(self) -> int:
        return self._c_spec_rounds.value

    @property
    def spec_drafted(self) -> int:
        return self._c_spec_drafted.value

    @property
    def spec_accepted(self) -> int:
        return self._c_spec_accepted.value

    @property
    def spec_emitted(self) -> int:
        return self._c_spec_emitted.value

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from shared pages
        (0.0 before any admission — never a division error)."""
        return safe_ratio(self._c_cached.value, self._c_prompt.value)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> Optional[Request]:
        """Enqueue a request; it is admitted as soon as a slot + pages free.

        Raises :class:`PagePoolExhausted` immediately (before the request
        enters the queue) if its prompt could never be served — so one
        oversized request cannot abort a run with valid requests in
        flight. A request whose *generation* outgrows an undersized page
        pool later is finished as truncated, not errored (see
        :meth:`_decode_step`).

        With a bounded queue (``max_queue``), overflow sheds the
        lowest-priority request with ``finish_reason = LoadShedded`` —
        returned here (possibly ``req`` itself) so callers can observe
        the drop; ``None`` when nothing was shed. ``arrival`` is stamped
        with the current engine step when unset (the deadline clock)."""
        # a recovered / re-submitted request's generated tokens are part
        # of the prompt it will re-prefill with — account for them
        self.kv.check_admissible(len(req.tokens) + len(req.out_tokens))
        if req.arrival is None:
            req.arrival = self.step_count
        if req.arrival_ts is None:
            req.arrival_ts = time.perf_counter()
        victim = self.scheduler.submit(req)
        if victim is not req:
            self._obs_request_start(req)
        if victim is not None:
            if victim.finish_step is None:
                victim.finish_step = self.step_count
            _observe_request(self.obs, victim)
        return victim

    def requeue(self, req: Request) -> None:
        """Re-admit a request the system already accepted (crash recovery
        from another replica): exempt from the queue bound — rescuing a
        request must never shed it — and placed at the queue front. The
        caller accounts the retry (the router does, for its backoff)."""
        self.kv.check_admissible(len(req.tokens) + len(req.out_tokens))
        if req.arrival is None:
            req.arrival = self.step_count
        if req.arrival_ts is None:
            req.arrival_ts = time.perf_counter()
        self.scheduler.requeue(req, front=True, count_retry=False)
        self._obs_request_start(req)

    def _obs_request_start(self, req) -> None:
        """Open (or re-annotate) the request's lifecycle trace span.

        All request spans live on the dedicated ``REQUEST_PID`` track,
        keyed by the scheduler sequence number — a request that migrates
        replicas after a crash stays one span, with a ``requeued``
        marker at each re-admission."""
        tr = self.obs.tracer
        if not tr.enabled or req._seq < 0:
            return
        rid = req._seq
        if getattr(req, "_obs_traced", False):
            tr.request_instant(rid, f"req {rid}", "requeued")
        else:
            req._obs_traced = True
            tr.request_begin(rid, f"req {rid}",
                             {"prompt": len(req.tokens),
                              "max_new": req.max_new_tokens,
                              "priority": req.priority})

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve all requests to completion (continuous batching)."""
        for r in requests:
            self.submit(r)
        self.run_until_idle()
        return requests

    # Steps tolerated with work pending but nothing progressing before
    # run_until_idle errors out. Non-zero because transiently-held pages
    # (fault injection / an external pool holder) legitimately stall the
    # engine; bounded so a genuine livelock still fails loudly.
    STALL_LIMIT = 512

    def run_until_idle(self) -> None:
        """Step until queue and slots are empty."""
        stalled = 0
        while self.scheduler.has_work:
            if self.step():
                stalled = 0
            else:
                stalled += 1
                if stalled > self.STALL_LIMIT:
                    raise RuntimeError(
                        f"engine made no progress in {stalled} steps "
                        f"({self.kv.occupancy()})")

    def jit_entry_points(self) -> Dict[str, object]:
        """Named jitted callables of the serving hot path.

        The recompile guard (:mod:`repro.analysis.recompile`) reads each
        one's ``_cache_size()`` to assert exactly one compile per
        (entry point, shape class) across a mixed workload."""
        eps = {"prefill": self._jit_prefill, "decode": self._jit_decode,
               "verify": self._jit_verify, "sample": self._jit_sample}
        for name in ("_draft_greedy", "_draft_probs"):
            fn = getattr(self.drafter, name, None)
            if fn is not None:
                eps["draft" + name[len("_draft"):]] = fn
        return eps

    @property
    def pressure(self) -> float:
        """Current page-pool pressure in [0, 1] (see PagedKVCache)."""
        return self.kv.pressure

    @property
    def progress_marker(self):
        """Monotone work counter — the router watchdog compares this
        between steps to detect a stalled (alive but useless) replica."""
        return self.prefilled_tokens + self.emitted_tokens

    def _update_degradation(self) -> None:
        """Advance the degradation ladder from the current pressure."""
        if self.degradation is None:
            return
        new = self.degradation.mode_for(self.pressure, self.mode)
        if new != self.mode:
            log.info("degradation %s -> %s (pressure %.2f, %s)",
                     MODE_NAMES[self.mode], MODE_NAMES[new],
                     self.pressure, self.kv.occupancy())
            self._c_transitions.inc()
            self.obs.annotate("degradation", frm=MODE_NAMES[self.mode],
                              to=MODE_NAMES[new],
                              pressure=round(self.pressure, 3))
        self.mode = new

    def step(self) -> bool:
        """One engine iteration: admit, one prefill chunk, one decode step.

        Running at most one prefill chunk per iteration bounds the decode
        stall any prompt can cause to ``prefill_chunk`` tokens of work.
        Under pressure the degradation ladder sheds work first: mode 1
        drops speculative lookahead, mode 2 shrinks the prefill budget,
        mode 3 stops admitting (docs/robustness.md).
        Returns False when there was nothing to do.
        """
        self._update_degradation()
        self._c_mode[self.mode].inc()
        obs = self.obs
        if obs.active:        # pool gauges: host ints, but O(pages) scans
            self._g_pool_bytes.set(float(self.kv.live_bytes))
            self._g_pressure.set(self.pressure)
            self._g_mode.set(float(self.mode))
            obs.track("pool.pressure", self.pressure)
        with obs.phase("admit"):
            for req in self.scheduler.expire_deadlines(self.step_count,
                                                       self.kv):
                log.info("request expired past deadline_steps=%s",
                         req.deadline_steps)
                _observe_request(obs, req)
            for s in self.scheduler.slots:   # expiry may have freed lanes
                if s.free:
                    self._set_slot_temp(s.idx, 0.0)
            # Admission stops at the top of the ladder — but never on an
            # idle engine (nothing running = nothing will release pages,
            # so waiting would deadlock; pressure on an idle pool is ~0
            # anyway unless pages are held externally, and then admit()
            # simply waits).
            if (self.mode < MODE_STOP_ADMIT
                    or not self.scheduler.occupied_slots()):
                for slot in self.scheduler.admit(self.kv):
                    self._set_slot_temp(slot.idx, slot.req.temperature)
                    self._c_prompt.inc(slot.prefill_len)
                    self._c_cached.inc(slot.pos)  # admission: pos = matched
        progressed = False
        slot = self.scheduler.next_prefill()
        if slot is not None:
            with obs.phase("prefill_chunk"):
                self._prefill_chunk_step(slot)
            progressed = True
        if self.scheduler.decode_slots():
            if self.spec is not None and self.mode < MODE_NO_SPEC:
                self._spec_decode_step()
            else:
                self._decode_step()
            progressed = True
        self.step_count += 1
        return progressed

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _evict(self, slot) -> None:
        """Evict + clear the lane's temperature (device buffer refresh)."""
        self.scheduler.evict(slot, self.kv)
        self._set_slot_temp(slot.idx, 0.0)

    def _reserve_lookahead(self, slot_idx: int, pos: int, kk: int) -> int:
        """Reserve pages for ``kk`` draft tokens past the pending one,
        shrinking ``kk`` instead of preempting when the pool runs short
        (speculation is opportunistic). Returns the reserved lookahead."""
        while kk > 0:
            try:
                self.kv.ensure(slot_idx, pos + kk + 1)
                return kk
            except PagePoolExhausted:
                kk -= 1
        return 0

    def _ensure_pages(self, slot_idx: int, n_tokens: int) -> None:
        """Grow a slot to n_tokens, preempting other slots if needed."""
        while True:
            try:
                self.kv.ensure(slot_idx, n_tokens)
                return
            except PagePoolExhausted:
                victim = self.scheduler.preempt_youngest(
                    self.kv, exclude=slot_idx)
                if victim is None:
                    raise
                self._set_slot_temp(victim.idx, 0.0)

    @property
    def prefill_budget(self) -> int:
        """Prompt tokens fed per prefill chunk — the full static chunk
        width normally; divided by the policy's ``chunk_divisor`` in
        degradation mode >= 2 (the compiled chunk SHAPE never changes,
        only how much of it carries real tokens, so no recompilation)."""
        if self.degradation is None or self.mode < MODE_SHRINK_PREFILL:
            return self.prefill_chunk
        return max(self.degradation.min_chunk,
                   self.prefill_chunk // self.degradation.chunk_divisor)

    def _prefill_chunk_step(self, slot) -> None:
        c = self.prefill_chunk           # static compiled width, never shrunk
        chunk = self.scheduler.prompt_chunk(
            slot, min(c, self.prefill_budget))
        valid = len(chunk)
        # prompt pages were committed in full by SlotScheduler.admit() —
        # only decode grows a slot page-by-page
        toks = np.zeros((1, c), np.int32)
        toks[0, :valid] = chunk
        with self._mesh_scope():
            logits, self.kv.data = self._jit_prefill(
                self.params, jnp.asarray(toks), self.kv.data,
                self.kv.table_device(self._table_sharding), _i32(slot.idx),
                _i32(slot.pos), _i32(valid))
        slot.pos += valid
        self._c_prefilled.inc(valid)
        # index the prompt pages this chunk completed: from here on other
        # requests sharing the prefix can map them instead of recomputing
        self.kv.register_prefix(slot.idx, slot.prompt, slot.pos)
        if slot.pos < slot.prefill_len:
            return
        req = slot.req
        temps = (jnp.asarray([req.temperature], jnp.float32)
                 if req.temperature > 0.0 else None)
        tok = int(self._device_read(
            self._sample(logits, temps, [slot.idx]))[0])
        self.scheduler.finish_prefill(slot, tok)
        self._record_token(slot, tok)

    def _grow_or_shed(self, s) -> None:
        """Reserve the page covering slot ``s``'s next write position,
        preempting neighbours if needed. When even that fails: an
        undersized pool that can NEVER hold the sequence finishes the
        request as truncated (the last sampled token is already in
        out_tokens and needs no cache write); a pool that could hold it
        but whose pages are transiently held elsewhere (fault injection /
        an external holder) preempts the slot itself — the request
        requeues and resumes token-identically once pages return."""
        try:
            self._ensure_pages(s.idx, s.pos + 1)
        except PagePoolExhausted:
            if self.kv.pages_for(s.pos + 1) > \
                    self.kv.table.allocator.num_pages:
                req = s.req          # _evict clears slot.req
                req.finish(FinishReason.TRUNCATED, self.step_count)
                self._evict(s)
                _observe_request(self.obs, req)
            else:
                self.scheduler.preempt(s, self.kv)
                self._set_slot_temp(s.idx, 0.0)

    def _decode_step(self) -> None:
        for s in list(self.scheduler.decode_slots()):
            if s.phase is not SlotPhase.DECODE:
                continue          # preempted by an earlier ensure this loop
            # the page covering the write position must exist up front
            self._grow_or_shed(s)
        dslots = self.scheduler.decode_slots()  # preemption may have culled
        if not dslots:
            return
        b = self.num_slots
        toks = np.zeros((b, 1), np.int32)
        # -1 marks lanes that are NOT decoding this step (free slots and
        # slots mid-prefill): decode_paged redirects their KV writes to
        # the trash page/row instead of through their page tables.
        positions = np.full((b,), -1, np.int32)
        for s in dslots:
            toks[s.idx, 0] = s.next_token
            positions[s.idx] = s.pos
        # device-resident per-slot temps: refreshed on admission/eviction,
        # NOT rebuilt and re-uploaded every decode step
        temps = self._decode_temps()
        with self._mesh_scope():
            with self.obs.phase("decode"):
                logits, self.kv.data = self._jit_decode(
                    self.params, jnp.asarray(toks), self.kv.data,
                    self.kv.table_device(self._table_sharding),
                    jnp.asarray(positions))
            with self.obs.phase("sample"):
                self.key, nxt_dev = self._jit_sample(self.key, logits,
                                                     temps)
        nxt = self._device_read(nxt_dev)
        for s in dslots:
            s.pos += 1
            self._record_token(s, int(nxt[s.idx]))

    # ------------------------------------------------------------------
    # speculative decoding (docs/speculative.md)
    # ------------------------------------------------------------------
    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target accepted (0.0 before
        any verify round — never a division error)."""
        return safe_ratio(self._c_spec_accepted.value,
                          self._c_spec_drafted.value)

    @property
    def tokens_per_verify(self) -> float:
        """Mean tokens emitted per verify call (1.0 = no speculation win;
        0.0 before any round)."""
        return safe_ratio(self._c_spec_emitted.value,
                          self._c_spec_rounds.value)

    def _spec_decode_step(self) -> None:
        """One draft/verify round over every decoding slot.

        Replaces :meth:`_decode_step` when ``spec_decode`` is configured:
        the drafter proposes up to ``k`` tokens per slot, ONE
        ``verify_paged`` call scores them all (k+1 static token columns;
        under-drafted slots pad with trash-redirected columns), and the
        accepted prefix plus one target-distribution token is recorded —
        token-identical to sequential greedy decoding when temperature
        is 0. Rejected rows roll back: ``slot.pos`` simply does not
        advance over them and :meth:`PagedKVCache.trim` drops tail pages
        the rejected lookahead no longer needs.
        """
        # page for the committed pending token: same rules as _decode_step
        # (preemption allowed; truncate-finish only when the pool can
        # never supply it, self-preempt when pages are transiently held)
        for s in list(self.scheduler.decode_slots()):
            if s.phase is not SlotPhase.DECODE:
                continue
            self._grow_or_shed(s)
        dslots = self.scheduler.decode_slots()
        if not dslots:
            return
        k = self.spec.k
        # Draft lookahead is capped by sequence room and the slot's
        # remaining generation budget (no proposal that could never be
        # recorded), and its page reservations are OPPORTUNISTIC: shrink
        # the lookahead rather than preempt a neighbour for speculation.
        # A drafter that writes draft KV through the page tables needs
        # its pages reserved BEFORE drafting; host-side drafters reserve
        # after proposing, so a no-proposal round allocates nothing.
        k_slot = {}
        for s in dslots:
            room = self.max_seq - s.pos - 1
            budget = s.req.max_new_tokens - len(s.req.out_tokens) - 1
            kk = max(0, min(k, room, budget))
            if self.drafter.writes_kv:
                kk = self._reserve_lookahead(s.idx, s.pos, kk)
            k_slot[s.idx] = kk
        with self.obs.phase("draft"):
            g, n_prop, q_rows = self.drafter.propose(self, dslots, k_slot,
                                                     k)
        if not self.drafter.writes_kv:
            for s in dslots:
                n_prop[s.idx] = self._reserve_lookahead(
                    s.idx, s.pos, int(n_prop[s.idx]))
        b = self.num_slots
        toks = np.zeros((b, k + 1), np.int32)
        posv = np.full((b,), -1, np.int32)
        nlive = np.zeros((b,), np.int32)
        for s in dslots:
            n = int(n_prop[s.idx])
            toks[s.idx, 0] = s.next_token
            toks[s.idx, 1:1 + n] = g[s.idx, :n]
            posv[s.idx] = s.pos
            nlive[s.idx] = n + 1
        with self._mesh_scope():
            with self.obs.phase("verify"):
                logits, ids, self.kv.data = self._jit_verify(
                    self.params, jnp.asarray(toks), self.kv.data,
                    self.kv.table_device(self._table_sharding),
                    jnp.asarray(posv), jnp.asarray(nlive))
        # all-greedy rounds pull only the (B, k+1) argmax ids; the full
        # logits tensor rides the SAME single transfer only when a
        # temperature slot needs distributions for rejection sampling
        need_q = any(s.req.temperature > 0.0 for s in dslots)
        got = self._device_read((ids, logits) if need_q else (ids,))
        ids_h, lg = got[0], (got[1] if need_q else None)
        self._c_spec_rounds.inc()
        for s in dslots:
            n = int(n_prop[s.idx])
            draft = [int(t) for t in g[s.idx, :n]]
            rows = None if q_rows is None else \
                [q_rows[t][s.idx] for t in range(n)]
            accepted, out = accept_tokens(
                draft, None if lg is None else lg[s.idx, :n + 1],
                s.req.temperature, self._spec_rng, rows,
                targets=ids_h[s.idx, :n + 1])
            self._c_spec_drafted.inc(n)
            self._c_spec_accepted.inc(accepted)
            req = s.req              # _record_token may evict (slot.req=None)
            for tok in out:
                s.pos += 1
                self._record_token(s, tok)
                self._c_spec_emitted.inc()
                if req.done:         # EOS/budget/truncation: drop the rest
                    break
            if not req.done:
                # Roll back the rejected lookahead: pages wholly past the
                # working set (committed rows plus the pending token's
                # write row) return to the pool. On a fully accepted
                # round `pos` advanced over everything the draft
                # reserved, so this is a no-op on the hot path; it only
                # fires — and only ever releases fresh refcount-1 draft
                # pages — when rejection left a page boundary behind.
                self.kv.trim(s.idx, s.pos + 1)

    def _record_token(self, slot, tok: int) -> None:
        """Append a sampled token and apply the eviction rules."""
        req = slot.req
        req.out_tokens.append(tok)
        self._c_emitted.inc()
        if req.first_token_step is None:
            req.first_token_step = self.step_count
            req.first_token_ts = time.perf_counter()
        slot.next_token = tok
        hit_eos = self.eos_id is not None and tok == self.eos_id
        budget_done = len(req.out_tokens) >= req.max_new_tokens
        truncated = slot.pos >= self.max_seq      # no room for another write
        if hit_eos or budget_done or truncated:
            req.finish(FinishReason.COMPLETED if (hit_eos or budget_done)
                       else FinishReason.TRUNCATED, self.step_count)
            self._evict(slot)
            _observe_request(self.obs, req)


class BatchToCompletionEngine:
    """Legacy fixed-batch engine (serve_bench baseline).

    Requests are grouped into batches of ``batch_size``, prefilled together
    (LEFT-padded / right-aligned) and decoded step-by-step until every
    request in the batch finishes — the whole batch waits on its longest
    member, and the dense cache is ``(batch, max_seq)`` regardless of
    occupancy. Pad rows are attention-masked via ``pad_lens`` (see module
    docstring). Attention-family models only (an SSM integrates pad inputs
    into its state; use :class:`Engine`, which never left-pads).
    """

    def __init__(self, model, params, qc: QuantConfig = DENSE,
                 batch_size: int = 8, max_seq: int = 512,
                 eos_id: Optional[int] = None, seed: int = 0,
                 obs: Optional[Obs] = None):
        if model.cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                "BatchToCompletionEngine left-pads prompts, which an SSM "
                "would integrate into its state (kv_start only masks "
                "attention); use the continuous Engine for "
                f"family={model.cfg.family!r}")
        if model.cfg.head_layout == "hd":
            raise ValueError(
                "BatchToCompletionEngine needs kv_start masking for "
                "mixed-length batches, which head_layout='hd' does not "
                "implement — the failure would otherwise be data-dependent "
                "(first unequal-length batch)")
        self.model = model
        self.params = params
        self.qc = qc
        self.batch_size = batch_size
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        # monotone step clock across batches: one tick per prefill and per
        # decode step, so Request.first_token_step / finish_step are
        # comparable with the continuous engine's step_count timestamps.
        self.step_count = 0
        # same latency accounting as the continuous engine (the ``req.*``
        # families land in obs.metrics), so serve_demo/serve_bench report
        # both engines from one registry surface
        self.obs = obs if obs is not None else Obs()

        self._prefill = jax.jit(
            lambda p, b, c, pl: model.prefill(p, b, c, qc, pad_lens=pl))
        self._decode = jax.jit(
            lambda p, t, c, pl: model.decode(p, t, c, qc, pad_lens=pl),
            donate_argnums=(2,))

    def _sample(self, logits: jax.Array,
                temps: Optional[jax.Array]) -> jax.Array:
        """Per-row sampling via :func:`_sample_tokens` (row index = slot)."""
        self.key, toks = _sample_tokens(
            self.key, logits, temps, range(logits.shape[0]))
        return toks

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve all requests (in submission-order batches of batch_size)."""
        for r in requests:
            if r.arrival is None:
                r.arrival = self.step_count
            if r.arrival_ts is None:
                r.arrival_ts = time.perf_counter()
        for i in range(0, len(requests), self.batch_size):
            self._run_batch(requests[i:i + self.batch_size])
        return requests

    def _run_batch(self, reqs: List[Request]) -> None:
        b = len(reqs)
        pad_b = self.batch_size
        max_prompt = max(len(r.tokens) for r in reqs)
        if max_prompt > self.max_seq:
            raise ValueError(
                f"prompt of {max_prompt} tokens exceeds max_seq="
                f"{self.max_seq}")
        toks = np.zeros((pad_b, max_prompt), np.int32)
        pad_h = np.full((pad_b,), max_prompt, np.int32)  # empty rows: all pad
        for j, r in enumerate(reqs):
            # left-pad / right-align so the batch decodes from one position
            toks[j, max_prompt - len(r.tokens):] = r.tokens
            pad_h[j] = max_prompt - len(r.tokens)
        # uniform-length batches need no mask: pass None so the model keeps
        # the static kv_start==0 fast path (identical HLO to the original
        # engine; filler rows b..pad_b are discarded anyway)
        pad_lens = jnp.asarray(pad_h) if pad_h[:b].any() else None
        cache = self.model.init_cache(pad_b, self.max_seq)
        logits, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(toks)}, cache, pad_lens)
        self.step_count += 1

        active = np.ones(pad_b, bool)
        active[b:] = False
        max_new = max(r.max_new_tokens for r in reqs)
        # per-request temperature, moved to device once (not per step)
        temps_h = np.zeros(pad_b, np.float32)
        temps_h[:b] = [r.temperature for r in reqs]
        temps = jnp.asarray(temps_h) if (temps_h > 0.0).any() else None
        next_tok = self._sample(logits, temps)
        for step in range(max_new):
            np_tok = np.asarray(next_tok)
            for j, r in enumerate(reqs):
                if active[j] and not r.done:
                    t = int(np_tok[j])
                    r.out_tokens.append(t)
                    if r.first_token_step is None:
                        r.first_token_step = self.step_count
                        r.first_token_ts = time.perf_counter()
                    if (self.eos_id is not None and t == self.eos_id) or \
                            len(r.out_tokens) >= r.max_new_tokens:
                        r.finish(FinishReason.COMPLETED, self.step_count)
                        active[j] = False
            if not active[:b].any():
                break
            if max_prompt + step >= self.max_seq:
                break             # cache full: truncate instead of letting
                #                   clamped writes silently corrupt row T-1
            logits, cache = self._decode(
                self.params, jnp.asarray(np_tok)[:, None], cache, pad_lens)
            self.step_count += 1
            next_tok = self._sample(logits, temps)
        for r in reqs:
            # anything still unfinished was truncated at max_seq: stamp
            r.finish(FinishReason.TRUNCATED, self.step_count)
            _observe_request(self.obs, r)


def greedy_generate(model, params, prompt_tokens, n_new: int,
                    qc: QuantConfig = DENSE, max_seq: int = 256):
    """Convenience one-shot greedy generation (tests/examples).

    Returns the list of ``n_new`` generated token ids."""
    eng = Engine(model, params, qc, batch_size=1, max_seq=max_seq)
    req = Request(tokens=list(prompt_tokens), max_new_tokens=n_new)
    eng.run([req])
    return req.out_tokens
