"""Serving engine: batched prefill + decode with per-sequence completion,
greedy/temperature sampling, and padded-vocab masking.

The engine owns the jitted step primitives — ``prefill_step``,
``prefill_chunk_step``, ``decode_step``, ``sample`` — and two consumers share
them: the static-batch :meth:`Engine.generate` below (pads every request to
the slowest sequence) and the continuous-batching
:class:`repro.serve.scheduler.Scheduler` (slot-based, in-flight admission).

Each step function is traced under a :func:`repro.dispatch.phase_scope`, so
every sparse-operator lookup inside resolves a phase-tagged OpKey: prefill
([B*S]-row operands) and decode ([B]-row operands) get separately profiled,
separately pinned implementations (TensorRT-LLM-style per-phase operator
specialization).  The same decode_step the multi-pod dry-run compiles for 512
chips drives this engine; on CPU it serves the reduced configs for
tests/examples.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry as reg
from repro.obs import trace as _ot


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    eos_id: Optional[int] = None
    seed: int = 0
    # profile sparse-operator candidates at engine build (otherwise the plan
    # is resolved from the existing profile DB / platform heuristic; also
    # switchable via REPRO_DISPATCH_PROFILE=1)
    profile_dispatch: Optional[bool] = None
    dispatch_batch_hint: int = 8
    # expected prompt length for the prefill-phase row bucket
    # (prefill rows ~= batch * seq; decode rows ~= batch)
    dispatch_seq_hint: int = 128


def _phased(fn, phase: str, entry: Optional[str] = None):
    """Wrap a step fn so its jit trace runs inside a dispatch phase scope,
    under an ``engine.trace`` span naming the entry point (``phase`` when
    not given).  The body runs only when JAX traces the step, so the span
    marks each retrace (and how long tracing took) and costs nothing on a
    cached call."""
    entry = entry or phase

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        from repro import dispatch as _dispatch

        with _ot.span("engine.trace", entry=entry), \
                _dispatch.phase_scope(phase):
            return fn(*args, **kwargs)

    return wrapped


class Engine:
    def __init__(self, cfg: ModelConfig, params,
                 serve_cfg: Optional[ServeConfig] = None):
        self.cfg = cfg
        self.params = params
        # None => fresh per-instance config (a dataclass default instance
        # would be shared mutable state across every Engine)
        self.scfg = serve_cfg if serve_cfg is not None else ServeConfig()
        # Build-time operator dispatch: resolve (and optionally profile) the
        # implementation for every compressed layer shape before tracing, so
        # the phase-tagged lookups inside the traced steps hit a warm profile
        # DB and every process serving this model pins identical per-phase
        # backends.
        from repro import dispatch as _dispatch

        scfg = self.scfg
        with _ot.span("engine.build", arch=cfg.name):
            self.dispatch_plan = _dispatch.plan_params(
                params, batch_hint=scfg.dispatch_batch_hint,
                phase_hints={
                    "prefill": scfg.dispatch_batch_hint * scfg.dispatch_seq_hint,
                    "decode": scfg.dispatch_batch_hint,
                },
                profile=scfg.profile_dispatch)
        self._decode = jax.jit(_phased(reg.decode_fn(cfg), "decode"),
                               donate_argnums=(1,))
        self._prefill = jax.jit(_phased(reg.prefill_fn(cfg), "prefill"))
        self._prefill_chunk = None  # built lazily (attention families only)
        # paged-cache steps, built lazily per page size (serve.kv_pages tier)
        self._paged_decode = None
        self._prefill_packed = None
        self._paged_page_size = None

    # ------------------------------------------------------------------
    # Step primitives (shared by generate() and the continuous Scheduler)
    # ------------------------------------------------------------------

    def sample(self, logits: jax.Array, key) -> jax.Array:
        """Sample next tokens from [B, S, V] logits (last position)."""
        logits = logits[:, -1].astype(jnp.float32)
        v = self.cfg.vocab_size
        if self.cfg.padded_vocab != v:
            logits = logits.at[:, v:].set(-1e30)
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.scfg.temperature).astype(jnp.int32)

    # kept as an alias: pre-refactor callers used the private name
    _sample = sample

    def prefill_step(self, prompts: np.ndarray, max_len: int,
                     extras: Optional[Dict] = None):
        """Run the prompt through the model; returns (last-token logits,
        decode-ready cache sized for max_len)."""
        b, s = prompts.shape
        batch = {"tokens": jnp.asarray(prompts)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        logits, cache = self._prefill(self.params, batch)
        if cache is None:
            # recurrent/hybrid families: prefill == run the recurrence over
            # the prompt (state cache, not KV)
            cache = reg.cache_init_fn(self.cfg, b, max_len)()
            for t in range(s):
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(prompts[:, t : t + 1]),
                    jnp.asarray(t, jnp.int32),
                )
        else:
            # grow the KV cache to max_len for attention families
            cache = self._grow_cache(cache, b, max_len, s)
        return logits, cache

    def prefill_chunk_step(self, cache, tokens, start, with_logits=True):
        """Prefill one fixed-shape chunk of a prompt into a preallocated
        cache (scheduler admission path; attention families only).
        ``with_logits=False`` skips the unembed matmul — only the chunk
        holding the last prompt token needs logits."""
        if self._prefill_chunk is None:
            # no cache donation here: the scheduler feeds slot *views* of its
            # pool cache, and a full-extent slice (n_slots == 1) can alias
            # the pool's own buffer — donating it would delete the pool
            self._prefill_chunk = jax.jit(
                _phased(reg.prefill_chunk_fn(self.cfg), "prefill",
                        "prefill_chunk"),
                static_argnums=(4,))
        return self._prefill_chunk(self.params, cache, jnp.asarray(tokens),
                                   jnp.asarray(start, jnp.int32),
                                   bool(with_logits))

    def decode_step(self, cache, tokens, pos):
        """One decode step. tokens [B,1]; pos scalar or per-sequence [B]
        int32.  Returns (logits [B,1,V], cache).  The cache argument is
        donated — callers must rebind to the returned cache."""
        return self._decode(self.params, cache, tokens, pos)

    def _build_paged(self, page_size: int):
        """(Re)build the paged step pair for one physical page size.  The
        paged cache is owned exclusively by the scheduler (no slot views),
        so BOTH steps donate it and scatter in place."""
        if self._paged_page_size == page_size:
            return
        self._paged_decode = jax.jit(
            _phased(reg.paged_decode_fn(self.cfg, page_size), "decode",
                    "paged_decode"),
            donate_argnums=(1,))
        self._prefill_packed = jax.jit(
            _phased(reg.prefill_packed_fn(self.cfg, page_size), "prefill",
                    "prefill_packed"),
            donate_argnums=(1,))
        self._paged_page_size = page_size

    def paged_decode_step(self, cache, tokens, pos, tables, *, page_size):
        """One decode step against a paged cache. tokens [B,1]; pos [B];
        tables [B, n_max] int32.  The cache argument is donated — callers
        must rebind to the returned cache."""
        self._build_paged(page_size)
        return self._paged_decode(self.params, cache, jnp.asarray(tokens),
                                  jnp.asarray(pos, jnp.int32),
                                  jnp.asarray(tables, jnp.int32))

    def packed_prefill_step(self, cache, packed, tables, *, page_size):
        """Prefill a packed multi-prompt stream (kv_pages.PackedPrefill)
        into a paged cache in ONE exact-shape call — zero padded tokens.
        Returns (logits [n_new, 1, V] — one row per admitted prompt — and
        the cache with all K/V scattered through the page tables).  Donates
        the cache; retraces per distinct stream length."""
        self._build_paged(page_size)
        return self._prefill_packed(
            self.params, cache, jnp.asarray(packed.tokens),
            jnp.asarray(packed.slot_ids), jnp.asarray(packed.positions),
            jnp.asarray(tables, jnp.int32), jnp.asarray(packed.last_idx))

    # ------------------------------------------------------------------
    # Static-batch generation
    # ------------------------------------------------------------------

    def generate(self, prompts: np.ndarray, extras: Optional[Dict] = None) -> Dict:
        """prompts: [B, S_prompt] int32. Returns dict with tokens + timings.

        With ``eos_id`` set, positions after a sequence's EOS are masked to
        ``eos_id`` (never the live tokens the batch keeps sampling for the
        still-running sequences) and ``gen_lens[b]`` reports how many tokens
        sequence b actually generated (its EOS included).
        """
        cfg, scfg = self.cfg, self.scfg
        b, s = prompts.shape
        max_len = s + scfg.max_new_tokens
        key = jax.random.PRNGKey(scfg.seed)

        t0 = time.perf_counter()
        with _ot.span("engine.prefill", batch=b, seq=s):
            logits, cache = self.prefill_step(prompts, max_len, extras)
        t_prefill = time.perf_counter() - t0

        out = []
        done = np.zeros((b,), bool)
        gen_len = np.zeros((b,), np.int32)

        def record(tok: jax.Array) -> jax.Array:
            """Mask post-EOS samples, track done/lengths; returns the token
            that is both emitted and fed back to the next decode step."""
            t = np.asarray(tok)
            if scfg.eos_id is not None:
                t = np.where(done, scfg.eos_id, t)
            gen_len[:] += (~done)
            out.append(t)
            if scfg.eos_id is not None:
                done[:] |= t == scfg.eos_id
            return jnp.asarray(t)

        key, k0 = jax.random.split(key)
        tok = record(self.sample(logits, k0))
        t1 = time.perf_counter()
        with _ot.span("engine.decode_loop", batch=b,
                      budget=scfg.max_new_tokens) as dsp:
            steps = 0
            for i in range(scfg.max_new_tokens - 1):
                if done.all():
                    break
                pos = jnp.asarray(s + i, jnp.int32)
                logits, cache = self.decode_step(cache, tok[:, None], pos)
                key, kk = jax.random.split(key)
                tok = record(self.sample(logits, kk))
                steps += 1
            dsp.set(steps=steps)
        t_decode = time.perf_counter() - t1
        gen = np.stack(out, axis=1)
        return {
            "tokens": gen,
            "gen_lens": gen_len.copy(),
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_tok_s": gen.shape[1] * b / max(t_decode, 1e-9),
        }

    def _grow_cache(self, cache, b, max_len, cur_len):
        if cache is None:  # recurrent families need no growth
            full = reg.cache_init_fn(self.cfg, b, max_len)()
            return full
        if "k" in cache and cache["k"].ndim == 5 and cache["k"].shape[2] < max_len:
            full = reg.cache_init_fn(self.cfg, b, max_len)()
            for key in ("k", "v"):
                full[key] = full[key].at[:, :, :cur_len].set(cache[key])
            for key in ("xk", "xv"):
                if key in cache:
                    full[key] = cache[key]
            return full
        return cache
