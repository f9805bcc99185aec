"""Continuous-batching decode engine.

One engine owns a fixed number of *slots* (the batch dimension of a per-slot
cache, ``init_cache(..., per_slot=True)``).  Admission runs the model's
batched ``prefill`` — one jitted forward over the whole (bucket-padded)
prompt — then splices the resulting batch-1 cache into the slot; every
``tick`` runs one jitted ``decode_step`` over all slots and retires the ones
that hit EOS or their generation budget.  All device computations have
static shapes: the decode step compiles once per engine, prefill once per
prompt bucket, the slot splice once — slot membership changes never
recompile.

Retirement is leak-free by construction: admission overwrites the slot's
entire cache subtree (KV, positions, recurrent states) with the freshly
prefilled one, so no state from the previous occupant survives.

``attn_impl="paged"`` switches the KV layout to a shared page pool
(``serve.paged.PagePool`` + the Pallas ragged paged-decode kernel): slots no
longer reserve ``max_seq`` positions up front, admission is gated on page
*reservations* instead of ``prompt + max_gen <= max_seq``, and per-tick
decode cost is proportional to each slot's LIVE tokens, not
``n_slots x max_seq``.  A request may generate far past ``max_seq`` (the
prompt-prefill buffer) as long as its pages fit the pool.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import PagedLayout, decode_step, init_cache, init_params, prefill
from repro.models.attention import paged_put
from repro.models.config import ModelConfig
from repro.serve.paged import PagePool

__all__ = ["ServeEngine", "bucket_len"]

# template-cache key -> paged-pool key for the admission splice
_POOL_KEYS = (("k", "k_pool"), ("v", "v_pool"), ("k_scale", "k_scale_pool"), ("v_scale", "v_scale_pool"))


def bucket_len(n: int, lo: int = 8) -> int:
    """Smallest power-of-two bucket >= n (>= lo).  Power-of-two buckets keep
    the per-bucket prefill jit cache small and divide the recurrent chunk
    sizes (rwkv chunk=32, mamba chunk=256 — both powers of two)."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class _Slot:
    rid: int | None = None
    max_gen: int = 0
    generated: int = 0
    out: list = dataclasses.field(default_factory=list)
    active: bool = False
    pos: int = 0  # host mirror of the device index clock (next position to write)
    prompt: np.ndarray | None = None  # kept so preemption can re-prefill


class ServeEngine:
    """Slot-based continuous batching over one model replica."""

    def __init__(
        self,
        cfg: ModelConfig,
        params=None,
        *,
        n_slots: int = 4,
        max_seq: int = 64,
        eos_id: int | None = None,
        temperature: float = 0.0,
        seed: int = 0,
        attn_impl: str = "naive",
        wkv_impl: str = "chunked",
        min_bucket: int = 8,
        page_size: int = 8,
        pool_pages: int | None = None,
    ) -> None:
        """``attn_impl``: "naive"/"blocked"/"flash" pick the prefill attention
        implementation over the dense cache; "paged" additionally switches
        the cache to the paged layout (prefill math stays "naive") and routes
        decode through the Pallas paged kernel.  ``page_size``/``pool_pages``
        size the pool; the default pool matches the dense layout's HBM
        footprint (``n_slots * max_seq`` tokens) — same memory, but shared,
        so one slot may grow past ``max_seq``."""
        if attn_impl not in ("naive", "blocked", "flash", "paged"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        self.cfg = cfg
        self.params = params if params is not None else init_params(cfg, jax.random.PRNGKey(seed))
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.temperature = temperature
        self.min_bucket = min_bucket
        self.attn_impl = attn_impl
        self._seed = seed
        self.slots = [_Slot() for _ in range(n_slots)]
        if attn_impl == "paged":
            n_pages = pool_pages if pool_pages is not None else -(-n_slots * max_seq // page_size)
            self.layout: PagedLayout | None = PagedLayout(page_size=page_size, n_pages=n_pages)
            self.pool: PagePool | None = PagePool(self.layout, n_slots)
            self.cache = init_cache(cfg, n_slots, max_seq, per_slot=True, paged=self.layout)
            # prefill template: non-windowed, so every prompt position is
            # present for the page splice (windowed ring entries would be
            # lost for positions below the window — the paged pools keep
            # them and the kernel masks by window instead)
            tmpl_cfg = dataclasses.replace(cfg, windowed_cache=False)
            self._fresh1 = init_cache(tmpl_cfg, 1, max_seq, per_slot=True)
            self._prefill_impl = "naive"
        else:
            self.layout = None
            self.pool = None
            self.cache = init_cache(cfg, n_slots, max_seq, per_slot=True)
            self._fresh1 = init_cache(cfg, 1, max_seq, per_slot=True)  # prefill template
            self._prefill_impl = attn_impl
        self.last_tok = jnp.zeros((n_slots,), jnp.int32)
        self._key = jax.random.PRNGKey(seed + 1)
        # counters
        self.ticks = 0
        self.prefills = 0
        self.prefill_tokens = 0
        self.tokens_out = 0
        self.active_slot_ticks = 0
        self.preemptions = 0
        self.restores = 0
        # analytic decode-cost counter: KV positions attended per
        # global-attention layer, summed over ticks and slots.  Dense attends
        # the full (n_slots, max_seq) cache every tick; paged attends each
        # active slot's live tokens rounded up to page granularity.
        self.attended_key_tokens = 0
        # the most recent tick's slice of the two counters above — what a
        # per-tick cost model (benchmarks, obs) reads without differencing
        self.last_tick_attended = 0
        self.last_tick_active = 0

        def sample(logits, key):
            if temperature > 0.0:
                return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def decode_fn(params, cache, tok, key):
            if cfg.embeds_input:
                inp = jnp.take(params["embed"], tok, axis=0)
            else:
                inp = tok
            logits, cache = decode_step(params, cache, inp, cfg)
            return cache, sample(logits, key)

        def insert_fn(big, small, last_tok, b, tok):
            out = {"index": big["index"].at[b].set(small["index"][0])}
            if "body" in big:
                out["body"] = jax.tree.map(
                    lambda g, s: g.at[:, b].set(s[:, 0].astype(g.dtype)), big["body"], small["body"]
                )
            if "tail" in big:
                out["tail"] = jax.tree.map(lambda g, s: g.at[b].set(s[0].astype(g.dtype)), big["tail"], small["tail"])
            return out, last_tok.at[b].set(tok)

        def splice_paged_layer(big_layer, small_layer, b, dest, offs, stacked):
            """Dense batch-1 template layer cache -> the big paged cache.
            Attention layers scatter template positions 0..W-1 into their pool
            pages (pad positions land on the scratch page); recurrent layers
            splice row-wise exactly like the dense insert."""
            if "k_pool" in big_layer:
                out = {}
                W = dest.shape[0]
                for src, dst in _POOL_KEYS:
                    if dst not in big_layer:
                        continue
                    pool, vals = big_layer[dst], small_layer[src]
                    if stacked:  # (R, 1, S, ...) -> scatter (R, W, ...) layer by layer
                        out[dst] = jax.vmap(lambda p, v: paged_put(p, dest, offs, v))(pool, vals[:, 0, :W])
                    else:
                        out[dst] = paged_put(pool, dest, offs, vals[0, :W])
                return out
            if stacked:
                return jax.tree.map(lambda g, s: g.at[:, b].set(s[:, 0].astype(g.dtype)), big_layer, small_layer)
            return jax.tree.map(lambda g, s: g.at[b].set(s[0].astype(g.dtype)), big_layer, small_layer)

        def insert_paged_fn(big, small, last_tok, b, tok, dest, offs):
            out = {"index": big["index"].at[b].set(small["index"][0]), "pages": big["pages"]}
            if "body" in big:
                out["body"] = {
                    key: splice_paged_layer(big["body"][key], small["body"][key], b, dest, offs, True)
                    for key in big["body"]
                }
            if "tail" in big:
                out["tail"] = {
                    key: splice_paged_layer(big["tail"][key], small["tail"][key], b, dest, offs, False)
                    for key in big["tail"]
                }
            return out, last_tok.at[b].set(tok)

        prefill_impl = self._prefill_impl

        def make_prefill():
            def fn(params, cache, toks, lengths, key):
                logits, cache = prefill(params, cache, toks, lengths, cfg, prefill_impl, wkv_impl)
                return cache, sample(logits, key)

            return jax.jit(fn)

        self._decode = jax.jit(decode_fn)
        self._insert = jax.jit(insert_fn)
        self._insert_paged = jax.jit(insert_paged_fn)
        self._make_prefill = make_prefill
        self._prefill_by_bucket: dict[int, object] = {}

    def reset(self, seed: int | None = None) -> None:
        """Return the engine to its just-constructed state (fresh cache, all
        slots free, counters zeroed) while KEEPING the jit caches — A/B
        benchmark runs and repeated tests skip recompilation."""
        if seed is not None:
            self._seed = seed
        self.slots = [_Slot() for _ in range(self.n_slots)]
        self.cache = init_cache(self.cfg, self.n_slots, self.max_seq, per_slot=True, paged=self.layout)
        if self.pool is not None:
            # the outgoing run's accounting must balance before it is thrown
            # away — every A/B bench reset() is a leak audit of the run that
            # just finished (aborted runs still pass: held-by-one-slot is fine)
            self.pool.check_leak_free()
            self.pool = PagePool(self.layout, self.n_slots)
        self.last_tok = jnp.zeros((self.n_slots,), jnp.int32)
        self._key = jax.random.PRNGKey(self._seed + 1)
        self.ticks = self.prefills = self.prefill_tokens = 0
        self.tokens_out = self.active_slot_ticks = self.attended_key_tokens = 0
        self.last_tick_attended = self.last_tick_active = 0
        self.preemptions = self.restores = 0

    # -- state ---------------------------------------------------------------

    @property
    def has_active(self) -> bool:
        return any(s.active for s in self.slots)

    @property
    def free_slots(self) -> list[int]:
        return [b for b, s in enumerate(self.slots) if not s.active]

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _ship_table(self) -> None:
        """Push the host page table to the device cache when it changed."""
        if self.pool is not None and self.pool.dirty:
            self.cache["pages"] = jnp.asarray(self.pool.table)
            self.pool.dirty = False

    # -- admission -----------------------------------------------------------

    def admissible(self, prompt_len: int, max_gen: int) -> bool:
        """Could this request EVER run on this engine (regardless of current
        load)?  Dense: ``prompt + max_gen <= max_seq``.  Paged: the prompt
        fits the prefill buffer and the pages fit the pool."""
        if prompt_len < 1 or max_gen < 1:
            return False
        if self.pool is not None:
            return prompt_len <= self.max_seq and self.pool.fits(prompt_len, max_gen)
        return prompt_len + max_gen <= self.max_seq

    def can_admit_now(self, prompt_len: int, max_gen: int) -> bool:
        """Admissible AND a slot is free AND (paged) the pool can cover the
        worst-case page reservation right now.  The scheduler's backpressure
        gate: pool pressure defers admission, it never rejects."""
        if not self.admissible(prompt_len, max_gen) or not self.free_slots:
            return False
        if self.pool is not None:
            return self.pool.can_reserve(prompt_len, max_gen)
        return True

    def admit(self, rid: int, prompt: np.ndarray, max_gen: int) -> tuple[int, tuple | None]:
        """Prefill ``prompt`` into a free slot.  ``prompt``: (L,) int32 token
        ids, or (L, d) float embeddings for ``cfg.embeds_input`` archs.
        Returns (slot, finished) where ``finished`` is ``(rid, tokens)`` if
        the request already retired at admission (max_gen == 1 or instant
        EOS), else None."""
        free = self.free_slots
        if not free:
            raise RuntimeError("no free slot — admission must be gated on free_slots")
        L = int(prompt.shape[0])
        if max_gen < 1:
            raise ValueError("max_gen must be >= 1")
        b = free[0]
        if self.pool is not None:
            if L < 1 or L > self.max_seq:
                raise ValueError(f"prompt_len {L} exceeds the prefill buffer ({self.max_seq})")
            # reserve_or_fail re-raises the fits/can_reserve violations
            # (ValueError for never-fits, RuntimeError for transient
            # exhaustion) — admission must be gated on can_admit_now()
            self.pool.reserve_or_fail(b, L, max_gen)
            self.pool.allocate_prefix(b, L)
        elif L < 1 or L + max_gen > self.max_seq:
            raise ValueError(f"prompt_len {L} + max_gen {max_gen} exceeds max_seq {self.max_seq}")
        first = self._prefill_into_slot(b, prompt)
        st = self.slots[b]
        st.rid, st.max_gen, st.generated, st.out, st.active = rid, max_gen, 1, [first], True
        st.pos = L
        st.prompt = prompt
        self.tokens_out += 1
        if (self.eos_id is not None and first == self.eos_id) or st.generated >= st.max_gen:
            st.active = False
            if self.pool is not None:
                self.pool.release(b)
            return b, (rid, st.out)
        return b, None

    def _prefill_into_slot(self, b: int, tokens: np.ndarray) -> int:
        """Run the bucketed batched prefill for ``tokens`` and splice the
        batch-1 cache into slot ``b`` (pages must already be reserved +
        prefix-allocated for paged engines).  Returns the sampled token."""
        L = int(tokens.shape[0])
        bucket = bucket_len(L, self.min_bucket)
        if self.cfg.embeds_input:
            padded = np.zeros((1, bucket, tokens.shape[1]), np.float32)
            padded[0, :L] = tokens
        else:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :L] = tokens
        fn = self._prefill_by_bucket.get(bucket)
        if fn is None:
            fn = self._prefill_by_bucket[bucket] = self._make_prefill()
        small, tok = fn(self.params, self._fresh1, jnp.asarray(padded), jnp.array([L], jnp.int32), self._next_key())
        if self.pool is not None:
            # splice template positions 0..W-1 into the slot's pages; pad
            # positions (p >= L) scatter onto the trailing scratch page.
            # Their table lookup is clamped: the bucket may span more page
            # slots than the table row has, and np.where gathers eagerly.
            W = min(bucket, self.max_seq)
            ps = self.layout.page_size
            pidx = np.arange(W)
            row = self.pool.table[b]
            dest = np.where(pidx < L, row[np.minimum(pidx // ps, row.shape[0] - 1)], self.layout.n_pages)
            self.cache, self.last_tok = self._insert_paged(
                self.cache,
                small,
                self.last_tok,
                b,
                tok[0],
                jnp.asarray(dest.astype(np.int32)),
                jnp.asarray((pidx % ps).astype(np.int32)),
            )
            self._ship_table()
        else:
            self.cache, self.last_tok = self._insert(self.cache, small, self.last_tok, b, tok[0])
        self.prefills += 1
        self.prefill_tokens += L
        return int(tok[0])

    # -- preemption (paged: pages are the checkpoint) -------------------------

    def can_preempt(self, slot: int) -> bool:
        """An active PAGED slot whose live prefix still fits the prefill
        buffer can be evicted now and restored token-identically later."""
        st = self.slots[slot]
        return self.pool is not None and st.active and st.pos <= self.max_seq

    def preempt(self, slot: int) -> dict:
        """Evict an active slot: release its pages back to the pool and
        return an rng-free resume token.  No cache tensors are saved — the
        generated prefix IS the checkpoint: :meth:`restore` re-prefills
        ``prompt + out[:-1]`` (a deterministic forward pass) and re-seats
        the saved last token, which is bit-identical to never having been
        evicted for greedy (temperature 0) decoding."""
        if not self.can_preempt(slot):
            raise RuntimeError(f"slot {slot} cannot be preempted (inactive, dense, or prefix past the prefill buffer)")
        st = self.slots[slot]
        self.pool.release(slot)
        state = {
            "rid": st.rid,
            "prompt": st.prompt,
            "out": list(st.out),
            "generated": st.generated,
            "max_gen": st.max_gen,
            "pos": st.pos,
        }
        self.slots[slot] = _Slot()
        self.preemptions += 1
        return state

    def can_restore(self, state: dict) -> bool:
        if self.pool is None or not self.free_slots or state["pos"] > self.max_seq:
            return False
        return self.pool.can_reserve(state["pos"], state["max_gen"] - state["generated"] + 1)

    def restore(self, state: dict) -> int:
        """Re-seat a preempted request: reserve pages for the remaining
        budget, re-prefill the prompt + generated prefix, and overwrite the
        re-sampled tail token with the SAVED one so the continuation is
        token-identical to the uninterrupted run.  Returns the slot."""
        if self.pool is None:
            raise RuntimeError("restore requires a paged engine")
        free = self.free_slots
        if not free:
            raise RuntimeError("no free slot — restore must be gated on can_restore")
        b = free[0]
        prompt, out, pos = state["prompt"], state["out"], state["pos"]
        if self.cfg.embeds_input:
            embed = np.asarray(self.params["embed"])
            gen = embed[np.asarray(out[:-1], np.int64)] if len(out) > 1 else np.zeros((0, prompt.shape[1]), prompt.dtype)
            prefix = np.concatenate([np.asarray(prompt), gen.astype(prompt.dtype)], axis=0)
        else:
            prefix = np.concatenate([np.asarray(prompt, np.int32), np.asarray(out[:-1], np.int32)])
        if prefix.shape[0] != pos:
            raise RuntimeError(f"corrupt resume state: prefix {prefix.shape[0]} != pos {pos}")
        # same worst case as the original admission: pages_for(L + max_gen - 1)
        self.pool.reserve_or_fail(b, pos, state["max_gen"] - state["generated"] + 1)
        self.pool.allocate_prefix(b, pos)
        self._prefill_into_slot(b, prefix)
        self.last_tok = self.last_tok.at[b].set(int(out[-1]))  # rng-free resume: the saved token, not a resample
        st = self.slots[b]
        st.rid, st.max_gen, st.generated, st.active = state["rid"], state["max_gen"], state["generated"], True
        st.out = list(out)
        st.pos = pos
        st.prompt = state["prompt"]
        self.restores += 1
        return b

    # -- decode --------------------------------------------------------------

    def tick(self) -> list[tuple]:
        """One decode step over all slots; returns [(rid, tokens), ...] for
        requests that retired this tick."""
        n_active = sum(s.active for s in self.slots)
        attended = 0
        if self.pool is not None:
            ps = self.layout.page_size
            for b, st in enumerate(self.slots):
                if st.active:
                    self.pool.ensure(b, st.pos)  # allocate-on-write for this tick's K/V
                    # this tick attends st.pos + 1 live tokens, page-granular
                    attended += self.layout.pages_for(st.pos + 1) * ps
            self._ship_table()
        else:
            attended = self.n_slots * self.max_seq
        self.attended_key_tokens += attended
        self.last_tick_attended = attended
        self.last_tick_active = n_active
        self.cache, tok = self._decode(self.params, self.cache, self.last_tok, self._next_key())
        self.last_tok = tok
        self.ticks += 1
        self.active_slot_ticks += n_active
        tok_host = np.asarray(tok)
        finished = []
        for b, st in enumerate(self.slots):
            if not st.active:
                continue
            st.pos += 1
            t = int(tok_host[b])
            st.out.append(t)
            st.generated += 1
            self.tokens_out += 1
            if (self.eos_id is not None and t == self.eos_id) or st.generated >= st.max_gen:
                st.active = False
                if self.pool is not None:
                    self.pool.release(b)
                finished.append((st.rid, st.out))
        return finished

    # -- reporting -----------------------------------------------------------

    def metrics(self) -> dict:
        m = {
            "n_slots": self.n_slots,
            "ticks": self.ticks,
            "prefills": self.prefills,
            "prefill_tokens": self.prefill_tokens,
            "tokens_out": self.tokens_out,
            "preemptions": self.preemptions,
            "restores": self.restores,
            "attended_key_tokens": self.attended_key_tokens,
            "slot_utilization": self.active_slot_ticks / (self.ticks * self.n_slots) if self.ticks else 0.0,
        }
        if self.pool is not None:
            m["pool"] = self.pool.metrics()
        return m
