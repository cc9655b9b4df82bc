"""CurpServeDriver: batched autoregressive serving with CURP-durable
sessions.

The serving master is speculative state (model KV caches + live sessions);
durability comes from (a) witness-recorded session commits (1 RTT) and (b)
batched backup syncs — both via CurpSessionStore.  After a master crash the
driver restores sessions from the recovered store and REBUILDS the KV caches
by re-prefilling each live session's tokens (the compute-for-durability
trade CURP makes: journal bytes are tiny because state is recomputable).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import WitnessGeometry
from repro.core.telemetry import get_registry
from repro.models.config import ModelConfig
from repro.models.transformer import decode_step, init_decode_cache, init_params

from .kvstore import CurpSessionStore, SessionState


@dataclass
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 128
    commit_every: int = 1      # session commits per generated token
    f: int = 3
    sync_batch: int = 50
    n_shards: int = 1          # session partitions (one master group each)
    # Slot-table size for the session router: the unit of live migration
    # (CurpSessionStore.migrate_sessions / rebalance moves slots between
    # master groups with no serving pause on untouched slots).
    n_slots: int = 256
    # Witness table shape (S x W), threaded down to the Pallas kernels.
    witness_geometry: WitnessGeometry = field(default_factory=WitnessGeometry)
    # "python" (protocol-reference slot walk) or "device" (gang record
    # kernel; one dispatch per commit batch).
    witness_backend: str = "python"
    # Commit each decode step's sessions as ONE atomic cross-shard
    # mini-transaction (CurpSessionStore.txn) instead of the per-session
    # durable batch: a crash can never persist half a step's sessions.
    atomic_step_commit: bool = False


class CurpServeDriver:
    def __init__(self, cfg: ModelConfig, serve: ServeConfig,
                 params=None, seed: int = 0) -> None:
        assert cfg.can_decode, "serving needs a decoder"
        self.cfg = cfg
        self.serve = serve
        self.params = params if params is not None else init_params(
            cfg, jax.random.PRNGKey(seed)
        )
        self.store = CurpSessionStore(f=serve.f, sync_batch=serve.sync_batch,
                                      n_shards=serve.n_shards,
                                      geometry=serve.witness_geometry,
                                      witness_backend=serve.witness_backend,
                                      n_slots=serve.n_slots)
        self.sessions: Dict[str, SessionState] = {}
        self._decode = jax.jit(
            lambda p, b, c: decode_step(cfg, p, b, c)
        )
        self._reset_cache()
        self.tokens_served = 0
        reg = get_registry()
        self._m_tokens = reg.counter("serve.tokens")
        self._h_commit = reg.histogram("serve.commit_sessions")
        self._m_recoveries = reg.counter("serve.recoveries")
        self._m_replayed = reg.counter("serve.replayed_ops")

    def _reset_cache(self) -> None:
        self.cache = init_decode_cache(
            self.cfg, self.serve.max_batch, self.serve.max_seq,
        )
        self.slots: List[Optional[str]] = [None] * self.serve.max_batch

    # -- session management --------------------------------------------------------
    def submit(self, session_id: str, prompt: List[int]) -> None:
        s = SessionState(session_id, list(prompt))
        self.sessions[session_id] = s
        self.store.commit(s)
        slot = self.slots.index(None)
        self.slots[slot] = session_id
        # Feed all but the last token: step() feeds tokens[-1], keeping the
        # fed-token stream identical across normal and recovered runs.
        self._replay_tokens(slot, s.tokens[:-1])

    def _replay_tokens(self, slot: int, tokens: List[int]) -> None:
        """Feed tokens through decode to build this slot's KV/SSM state; the
        per-slot active mask keeps other sessions' caches and positions
        untouched (mixed-length batching)."""
        for t in tokens:
            batch = self._batch_for(slot, t)
            _, self.cache = self._decode(self.params, batch, self.cache)

    def _batch_for(self, slot: int, token: int) -> Dict[str, jnp.ndarray]:
        toks = np.zeros((self.serve.max_batch, 1), np.int32)
        toks[slot, 0] = token
        active = np.zeros((self.serve.max_batch,), np.int32)
        active[slot] = 1
        return {"tokens": jnp.asarray(toks), "active": jnp.asarray(active)}

    # -- decoding -----------------------------------------------------------------
    def step(self) -> Dict[str, int]:
        """One batched decode step for every live slot; commit via CURP."""
        live = [(i, sid) for i, sid in enumerate(self.slots) if sid]
        if not live:
            return {}
        last = np.zeros((self.serve.max_batch, 1), np.int32)
        active = np.zeros((self.serve.max_batch,), np.int32)
        for i, sid in live:
            last[i, 0] = self.sessions[sid].tokens[-1]
            active[i] = 1
        logits, self.cache = self._decode(
            self.params,
            {"tokens": jnp.asarray(last), "active": jnp.asarray(active)},
            self.cache,
        )
        out: Dict[str, int] = {}
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        to_commit: List[SessionState] = []
        for i, sid in live:
            tok = int(nxt[i])
            s = self.sessions[sid]
            s.tokens.append(tok)
            out[sid] = tok
            self.tokens_served += 1
            self._m_tokens.inc()
            if len(s.tokens) % self.serve.commit_every == 0:
                to_commit.append(s)
        # One batched CURP round for the whole decode step: distinct session
        # keys commute, so the batch completes via each shard's 1-RTT path.
        # With atomic_step_commit the step commits as ONE mini-transaction
        # instead (all-or-nothing across shards; single-shard steps keep the
        # 1-RTT short-circuit).
        self._h_commit.record(len(to_commit))
        if self.serve.atomic_step_commit:
            self.store.txn(to_commit)
        else:
            self.store.commit_batch(to_commit)
        return out

    def generate(self, n_tokens: int) -> None:
        for _ in range(n_tokens):
            self.step()

    # -- failures -----------------------------------------------------------------
    def crash_and_recover(self) -> Dict[str, int]:
        """Master (driver state) dies; sessions recover from CURP store; KV
        caches rebuild by re-prefill."""
        report = self.store.crash_and_recover()
        live_ids = [sid for sid in self.slots if sid]
        self.sessions = {}
        self._reset_cache()
        recovered = 0
        for sid in live_ids:
            s = self.store.load(sid)
            if s is None:
                continue
            self.sessions[sid] = s
            slot = self.slots.index(None)
            self.slots[slot] = sid
            self._replay_tokens(slot, s.tokens[:-1])
            recovered += 1
        self._m_recoveries.inc()
        self._m_replayed.inc(report.replayed)
        return {"recovered_sessions": recovered,
                "replayed_ops": report.replayed}
