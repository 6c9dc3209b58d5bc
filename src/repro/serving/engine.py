"""Real-execution serving engine: continuous batching over an actual JAX
model on one device (a TPU chip; reduced configs run on the CPU in tests).

One ``ServingEngine`` is one PaDG *instance*: it owns params, a slotted
KV cache on its device, and executes prefill/decode slots for the
scheduling ``Instance`` it is attached to.  The scheduler stack (macro
instance, Algorithms 1+2, mitosis) is exactly the one from
``repro.core`` — durations are measured wall-clock instead of predicted,
which is what `MeasuredExecutor` adapts.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.instance import Instance
from repro.core.request import Request, RequestState
from repro.models import forward, grow_cache, init_cache, init_params
from repro.obs.events import NULL_SPAN, NULL_TRACER
from repro.simulator.cost_model import (HARDWARE_BY_DEVICE_KIND,
                                        InstanceCostModel)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8            # decode slots
    max_seq_len: int = 256        # per-slot KV capacity
    dtype: object = jnp.float32
    eos_token: int = 1
    greedy: bool = True


class MeasuredExecutor:
    """ExecutorModel backed by observed wall-clock times, used by the
    scheduling Instance attached to a real engine.

    Shape-aware: predictions follow the same linear forms as
    ``simulator.cost_model`` (prefill base + per-token; decode per-slot
    base + ctx-sum term), with the constants seeded by probing a cost
    model (``seed_model``) and a single EWMA *gain* per op tracking the
    observed/predicted ratio — so a slot with twice the batch really is
    predicted to take longer, and the first prediction before any
    observation is the model's estimate rather than a magic number.
    """

    # no sliding-window clamp on the real engine's slotted KV: advertise
    # the Instance ctx_sum fast path with an unbounded clamp
    ctx_clamp = 0

    def __init__(self, seed_model=None,
                 fallback_prefill=2e-4, fallback_decode=5e-2):
        if seed_model is not None:
            p1 = seed_model.prefill_time([1])
            p257 = seed_model.prefill_time([257])
            self._prefill_per_tok = max((p257 - p1) / 256.0, 1e-12)
            self._prefill_base = max(p1 - self._prefill_per_tok, 0.0)
            d10 = seed_model.decode_time(1, [0])
            d20 = seed_model.decode_time(2, [0, 0])
            d1k = seed_model.decode_time(1, [1024])
            self._decode_per_seq = max(d20 - d10, 0.0)
            self._decode_per_ctx = max((d1k - d10) / 1024.0, 0.0)
            self._decode_base = max(d10 - self._decode_per_seq, 0.0)
        else:
            # legacy flat fallbacks (no model to probe)
            self._prefill_per_tok = fallback_prefill
            self._prefill_base = 0.0
            self._decode_per_seq = fallback_decode
            self._decode_per_ctx = 0.0
            self._decode_base = 0.0
        self._prefill_gain = 1.0
        self._decode_gain = 1.0

    def observe_prefill(self, tokens: int, dt: float) -> None:
        pred = self._prefill_base + self._prefill_per_tok * max(1, tokens)
        if pred > 0:
            self._prefill_gain = (0.7 * self._prefill_gain
                                  + 0.3 * dt / pred)

    def observe_decode(self, dt: float, batch: int = 1,
                       ctx_sum: int = 0) -> None:
        pred = (self._decode_base + self._decode_per_seq * max(1, batch)
                + self._decode_per_ctx * ctx_sum)
        if pred > 0:
            self._decode_gain = 0.7 * self._decode_gain + 0.3 * dt / pred

    def prefill_time(self, lens: List[int],
                     kv_prefix_lens: Optional[List[int]] = None) -> float:
        if not lens:
            return 0.0
        tokens = sum(lens) + (sum(kv_prefix_lens) if kv_prefix_lens else 0)
        return self._prefill_gain * (self._prefill_base
                                     + self._prefill_per_tok * tokens)

    def decode_time(self, batch: int, ctx_lens: Optional[List[int]] = None,
                    *, ctx_sum: Optional[int] = None) -> float:
        if batch == 0:
            return 0.0
        if ctx_sum is None:
            ctx_sum = sum(ctx_lens) if ctx_lens else 0
        return self._decode_gain * (self._decode_base
                                    + self._decode_per_seq * batch
                                    + self._decode_per_ctx * ctx_sum)


def _prefill_step(params, toks, *, cfg: ModelConfig):
    logits, cache = forward(params, cfg, {"tokens": toks}, return_cache=True)
    return logits[:, -1], cache


def _decode_step(params, cache, toks, lengths, *, cfg: ModelConfig):
    logits, cache = forward(params, cfg, {"tokens": toks},
                            cache=cache, cache_len=lengths)
    return logits[:, 0], cache


def serving_steps(cfg: ModelConfig):
    """The jitted programs a ``ServingEngine`` runs: ``prefill(params,
    toks (1, T))`` -> (last-position logits, cache of T positions) and
    ``decode(params, cache, toks (B, 1), lengths (B,))`` -> (logits, cache),
    the cache donated.  Named functions, so that a profiler trace shows
    the programs as ``jit_prefill_step`` and ``jit_decode_step``."""
    def prefill_step(params, toks):
        return _prefill_step(params, toks, cfg=cfg)

    def decode_step(params, cache, toks, lengths):
        return _decode_step(params, cache, toks, lengths, cfg=cfg)

    return jax.jit(prefill_step), jax.jit(decode_step, donate_argnums=(1,))


class ServingEngine:
    """Slot-based continuous batching with a fixed-shape decode step (no
    recompilation as requests come and go).

    Params, cache and step inputs live on ``device`` (the first device
    when None).  Without a ``cost_model`` the scheduler's seed model is
    looked up by the device's kind; a kind with no entry raises.

    With a flight recorder attached (``tracer``, ``iid`` its instance),
    each step is a span: ``step.prefill`` (prompt in, first token out),
    ``step.admit`` (the prefill cache copied into the request's slot) and
    ``step.decode`` (one decode iteration, sampled tokens fed back).
    """

    tracer = NULL_TRACER
    iid = 0

    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0,
                 econf: EngineConfig = EngineConfig(),
                 cost_model=None, recorder=None,
                 device: Optional[jax.Device] = None):
        assert not cfg.is_encoder, "decode engine serves decoder models"
        self.cfg = cfg
        self.econf = econf
        self.device = device if device is not None else jax.devices()[0]
        if cost_model is None:
            hw = HARDWARE_BY_DEVICE_KIND.get(self.device.device_kind)
            if hw is None:
                raise ValueError(
                    f"no seed cost model for device kind "
                    f"{self.device.device_kind!r}; pass cost_model=")
            cost_model = InstanceCostModel(cfg=cfg, hw=hw)
        self.params = params if params is not None else init_params(
            jax.random.key(seed), cfg, econf.dtype, device=self.device)
        B, S = econf.max_batch, econf.max_seq_len
        self.cache = init_cache(cfg, B, max_len=S, dtype=econf.dtype,
                                device=self.device)
        self.tokens = jnp.zeros((B, 1), jnp.int32, device=self.device)
        self.lengths = np.zeros(B, np.int32)          # context per slot
        self.slot_req: List[Optional[Request]] = [None] * B
        self.executor = MeasuredExecutor(seed_model=cost_model)
        self.recorder = recorder      # optional CalibrationRecorder
        self.prefill_fn, self.decode_fn = serving_steps(cfg)

    def warmup(self, prompt_lens) -> None:
        """Compile the prefill for each prompt length, the admission path
        and the decode step, outside any measured or scheduled time.
        Leaves every slot free and the executor untouched."""
        for n in sorted(set(prompt_lens)):
            toks = jax.device_put(np.zeros((1, n), np.int32), self.device)
            _, pcache = self.prefill_fn(self.params, toks)
            _merge_slot(self.cfg, self.cache,
                        grow_cache(self.cfg, pcache, self.econf.max_seq_len),
                        0)
        _, self.cache = self.decode_fn(
            self.params, self.cache, self.tokens,
            jax.device_put(self.lengths, self.device))
        jax.block_until_ready(self.cache)

    # --------------------------------------------------------------- #
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def prefill(self, req: Request) -> int:
        """Run the prompt through the model, land the request in a decode
        slot.  Returns the generated first token."""
        slots = self.free_slots()
        assert slots, "no free decode slot"
        slot = slots[0]
        prompt = req.prompt_tokens
        trc = self.tracer
        on = trc.enabled
        t0 = time.perf_counter()
        with (trc.span("step.prefill", iid=self.iid, rid=req.rid,
                       tokens=len(prompt)) if on else NULL_SPAN):
            toks = jax.device_put(np.asarray(prompt, np.int32)[None, :],
                                  self.device)
            logits, pcache = self.prefill_fn(self.params, toks)
            first = int(jnp.argmax(logits[0]))
        with (trc.span("step.admit", iid=self.iid, rid=req.rid, slot=slot)
              if on else NULL_SPAN):
            pcache = grow_cache(self.cfg, pcache, self.econf.max_seq_len)
            self.cache = _merge_slot(self.cfg, self.cache, pcache, slot)
            dt = time.perf_counter() - t0
            self.tokens = self.tokens.at[slot, 0].set(first)
        self.executor.observe_prefill(len(prompt), dt)
        if self.recorder is not None:
            self.recorder.record_prefill(len(prompt), dt)

        self.lengths[slot] = len(prompt)
        self.slot_req[slot] = req
        req.generated = [first]
        return first

    def decode_step(self) -> Dict[int, int]:
        """One decode iteration over all occupied slots.  Returns
        {slot: token} for slots that produced a token."""
        occupied = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not occupied:
            return {}
        ctx_sum = int(sum(self.lengths[i] for i in occupied))
        trc = self.tracer
        out: Dict[int, int] = {}
        with (trc.span("step.decode", iid=self.iid, batch=len(occupied),
                       ctx=ctx_sum) if trc.enabled else NULL_SPAN):
            t0 = time.perf_counter()
            lengths = jax.device_put(self.lengths, self.device)
            logits, self.cache = self.decode_fn(
                self.params, self.cache, self.tokens, lengths)
            new_tokens = np.asarray(jnp.argmax(logits, axis=-1))
            dt = time.perf_counter() - t0
            self.executor.observe_decode(dt, batch=len(occupied),
                                         ctx_sum=ctx_sum)
            if self.recorder is not None:
                self.recorder.record_decode(len(occupied), ctx_sum, dt)

            for i in occupied:
                tok = int(new_tokens[i])
                self.lengths[i] += 1
                out[i] = tok
                req = self.slot_req[i]
                req.generated.append(tok)
                self.tokens = self.tokens.at[i, 0].set(tok)
                done = (tok == self.econf.eos_token
                        or len(req.generated) >= req.output_len
                        or self.lengths[i] >= self.econf.max_seq_len - 1)
                if done:
                    self.slot_req[i] = None
                    self.lengths[i] = 0
        return out

    def release(self, req: Request) -> None:
        """Free the slot holding ``req`` (scheduler-side early finish,
        e.g. a one-token request done at prefill)."""
        for i, r in enumerate(self.slot_req):
            if r is req:
                self.slot_req[i] = None
                self.lengths[i] = 0
                return


def _merge_slot(cfg, big_cache, pcache, slot: int):
    """Write a prefill-produced (B=1) cache into batch slot `slot`."""
    def merge(big, small):
        # identify the batch axis: scan leaves are (n_full, B, ...) and the
        # single-request cache has B == 1 there; tail leaves are (B, ...)
        axis = 1 if (big.ndim >= 2 and small.ndim == big.ndim
                     and small.shape[0] == big.shape[0]
                     and small.shape[1] == 1) else 0
        # pad small's seq dim up to big's if needed
        pads = []
        for d in range(big.ndim):
            if d == axis:
                pads.append((0, 0))
            else:
                pads.append((0, big.shape[d] - small.shape[d]))
        small = jnp.pad(small, pads)
        idx = [slice(None)] * big.ndim
        idx[axis] = slice(slot, slot + 1)
        return big.at[tuple(idx)].set(small)

    return jax.tree.map(merge, big_cache, pcache)
