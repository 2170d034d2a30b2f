"""Serving engine: slot-based continuous batching over jit'd prefill/decode.

A fixed pool of B slots decodes in lockstep (one jit'd decode_step per
tick); finished/empty slots are refilled by prefilling the pending request
into the slot's cache lane. Prefix-dedup uses the paper's fingerprints:
identical prompts hit a logits cache instead of recomputing prefill.

On a real cluster the same engine runs per model replica; slots are the
intra-replica batch dim (sharded over 'data'), and the router process
assigns requests to replicas by... a Multilinear hash of the session id.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..hash import Hasher, HashSpec
from ..hash.service import TransportError

_PREFIX_KEY_SEED = 0x1E53


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray           # (T,) int32
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    #: admission verdict (None = not checked / no admission service;
    #: False = rejected as a duplicate, completed without decoding)
    admitted: bool | None = None


class ServeEngine:
    def __init__(self, api, params, *, n_slots: int = 4, max_seq: int = 256,
                 greedy: bool = True, mesh=None, admission=None,
                 admission_items: int | None = None,
                 tree_prompt_words: int = 1 << 12):
        self.api = api
        self.params = params
        self.B = n_slots
        self.S = max_seq
        cfg = api.cfg
        self._decode = jax.jit(
            lambda p, c, t, pos: api.decode_step(p, c, t, pos))
        self._prefill_cache = {}
        self._prefix_logit_cache: dict[int, np.ndarray] = {}
        self._prefix_hasher = Hasher.from_spec(HashSpec(
            family="multilinear", n_hashes=1, out_bits=64,
            variable_length=True, seed=_PREFIX_KEY_SEED))
        # pending prompts are fingerprinted across the mesh data axis (B/D
        # rows per device) and ASYNCHRONOUSLY: the launch is dispatched at
        # submit time, materialized only when _assign first needs a key, so
        # hashing overlaps prefill compute. mesh=None uses the live device
        # set (a 1-device mesh on CPU -- same code path).
        self._prefix_sharded = self._prefix_hasher.sharded(mesh)
        # prompts at/past this length take the mesh-parallel tree path
        # (repro.hash.tree) instead of padding the batched launch out to
        # the longest prompt; routing is by length alone, so a prompt's
        # key is stable across batch compositions
        self.tree_prompt_words = int(tree_prompt_words)
        self._mesh = mesh
        self._tree = None  # lazy TreeHasher; engines with short max_seq never build it
        self._pending_keys = None  # (req_ids, in-flight device array)
        self._req_key_cache: dict[int, int] = {}
        self.slots: list[Request | None] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int64)
        self.caches = api.init_caches(n_slots, max_seq)
        # optional fault-tolerant front door (repro.hash.service): duplicate
        # prompts are rejected before they cost a prefill; the engine keeps
        # serving through backend outages (DESIGN.md §8). `admission_items=`
        # builds one in-process: a single L2 shard whose filter is a
        # DeviceShardedBloom over the engine's mesh (its probes routed: one
        # all_to_all per wave).
        if admission is None and admission_items is not None:
            from ..hash.service import AdmissionService
            from ..parallel.sharding import data_mesh

            admission = AdmissionService.over_bloom_shards(
                1, int(admission_items),
                mesh=data_mesh() if mesh is None else mesh)
        self.admission = admission
        self.stats = {"prefix_hits": 0, "prefills": 0, "ticks": 0,
                      "degraded_ticks": 0, "l1_only_admits": 0,
                      "admission_rejects": 0, "admission_errors": 0}

    # -- prefix cache (paper fingerprints, DESIGN.md §3/§7) ------------------

    def _tree_hasher(self):
        if self._tree is None:
            from ..hash.tree import TreeHasher, TreeSpec

            self._tree = TreeHasher(TreeSpec(seed=_PREFIX_KEY_SEED),
                                    mesh=self._mesh)
        return self._tree

    def _prompt_key(self, prompt: np.ndarray) -> int:
        """64-bit fingerprint of one prompt. Short prompts: variable-length
        host path (bit-identical to the batched device path used in
        submit_all). Long prompts (>= tree_prompt_words): tree fingerprint
        -- same value the precompute path assigns them."""
        toks = prompt.astype(np.uint32)
        if len(toks) >= self.tree_prompt_words:
            return self._tree_hasher().fingerprint(toks)
        return int(self._prefix_hasher.hash_batch(
            [toks], backend="host")[0, 0])

    def _precompute_prompt_keys(self, requests: "list[Request]") -> None:
        """Fingerprint every pending prompt in ONE device-sharded hash
        launch, dispatched asynchronously (jax async dispatch: no host sync
        here; `_drain_prompt_keys` materializes on first use). Shapes are
        pow2-bucketed so varying request counts / prompt lengths reuse a
        bounded set of traces instead of compiling per submit_all.

        Prompts at/past `tree_prompt_words` are fingerprinted through the
        mesh-parallel tree path instead (one fused leaf launch each,
        straight into the key cache), so a single huge prompt neither
        inflates the batch pad width nor serializes into a host loop."""
        if not requests:
            return
        from ..kernels.autotune import pow2_at_least

        long_reqs = [r for r in requests
                     if len(r.prompt) >= self.tree_prompt_words]
        for r in long_reqs:
            self._req_key_cache[r.req_id] = self._tree_hasher().fingerprint(
                r.prompt.astype(np.uint32))
        requests = [r for r in requests
                    if len(r.prompt) < self.tree_prompt_words]
        if not requests:
            return
        prompts = [r.prompt.astype(np.uint32) for r in requests]
        n_pad = pow2_at_least(max((len(p) for p in prompts), default=1) or 1)
        b_pad = pow2_at_least(len(prompts))
        toks = np.zeros((b_pad, n_pad), np.uint32)
        lens = np.zeros(b_pad, np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
            lens[i] = len(p)
        self._prefix_sharded.ensure(n_pad)
        limbs = self._prefix_sharded(jnp.asarray(toks), jnp.asarray(lens))
        self._pending_keys = ([r.req_id for r in requests], limbs)

    def _drain_prompt_keys(self) -> None:
        """Materialize the in-flight fingerprint launch (one sync for the
        whole pending batch) into the per-request key cache."""
        if self._pending_keys is None:
            return
        req_ids, limbs = self._pending_keys
        self._pending_keys = None
        arr = np.asarray(limbs)[: len(req_ids)]  # (B, 1, 2) uint32 (hi, lo)
        fps = (arr[:, 0, 0].astype(np.uint64) << np.uint64(32)) | arr[:, 0, 1]
        for rid, fp in zip(req_ids, fps):
            self._req_key_cache[rid] = int(fp)

    # -- admission (fault-tolerant front door, DESIGN.md §8) -----------------

    def _admit_wave(self, reqs: "list[Request]") -> None:
        """Admission-check one slot-pool's worth of pending requests through
        the `AdmissionService` (L1/L2 filters + retry/breaker). Called with
        the NEXT wave while the current decode step is still in flight, so
        L2 round-trips overlap device compute. An admission outage the
        service itself could not absorb (its own transport errors) falls
        back to serving everything -- the engine's job is to answer
        requests. Any other error (a kernel that fails to lower, a bad
        argument) propagates: it is a bug, not an outage."""
        if self.admission is None:
            return
        todo = [r for r in reqs if r.admitted is None]
        if not todo:
            return
        try:
            mask = self.admission.admit_batch(
                [r.prompt.astype(np.uint32) for r in todo])
        except TransportError:
            self.stats["admission_errors"] += 1
            for r in todo:
                r.admitted = True
            return
        for r, ok in zip(todo, mask):
            r.admitted = bool(ok)
        self.stats["l1_only_admits"] = self.admission.stats["l1_only_admits"]

    # -- slot management -----------------------------------------------------

    def _assign(self, req: Request, slot: int):
        """Prefill a single request into slot `slot` of the batched cache."""
        T = len(req.prompt)
        self._drain_prompt_keys()
        key = self._req_key_cache.pop(req.req_id, None)
        if key is None:
            key = self._prompt_key(req.prompt)
        logits, cache1 = self.api.prefill(
            self.params, {"tokens": jnp.asarray(req.prompt[None], jnp.int32)},
            cache_len=self.S)
        if key in self._prefix_logit_cache:
            self.stats["prefix_hits"] += 1
        else:
            self._prefix_logit_cache[key] = np.asarray(logits[0])
        self.stats["prefills"] += 1
        # splice the single-row cache into the batched cache at `slot`.
        # Cache leaves under 'blocks' are layer-stacked: (n_blocks, B, ...),
        # so the slot dim is axis 1 there and axis 0 for tail leaves.
        def splice(path, full, one):
            in_blocks = any(str(getattr(k, "key", "")) == "blocks" for k in path)
            ax = 1 if in_blocks and full.ndim >= 2 else 0
            if one.ndim == full.ndim and full.shape[ax] == self.B:
                idx = (slice(None), slot) if ax == 1 else (slot,)
                src = one[(slice(None), 0)] if ax == 1 else one[0]
                return full.at[idx].set(src)
            return full
        self.caches = jax.tree_util.tree_map_with_path(splice, self.caches, cache1)
        self.slots[slot] = req
        self.slot_pos[slot] = T
        first = int(np.argmax(np.asarray(logits[0])))
        req.out_tokens.append(first)

    def submit_all(self, requests: list[Request]):
        # reject un-servable prompts up front, before any state is touched:
        # a prompt of max_seq tokens has no cache room for even one decode
        for r in requests:
            if len(r.prompt) >= self.S:
                raise ValueError(
                    f"request {r.req_id}: prompt length {len(r.prompt)} >= "
                    f"max_seq {self.S}; no decode budget -- raise max_seq "
                    "or truncate the prompt")
        pending = list(requests)
        self._admit_wave(pending[: self.B])  # first wave has no decode to hide behind
        self._precompute_prompt_keys(pending)
        try:
            while pending or any(s is not None for s in self.slots):
                # fill free slots (skipping admission-rejected requests --
                # they complete immediately with no tokens)
                for i in range(self.B):
                    while self.slots[i] is None and pending:
                        req = pending.pop(0)
                        if req.admitted is None:
                            self._admit_wave([req])
                        if req.admitted is False:
                            req.done = True
                            self.stats["admission_rejects"] += 1
                            continue
                        self._assign(req, i)
                if not any(s is not None for s in self.slots):
                    continue  # whole wave rejected; loop re-checks pending
                logits = self._tick_launch()
                # decode is in flight: admission-check the next wave on the
                # host while the device works (overlap, DESIGN.md §8)
                self._admit_wave(pending[: self.B])
                self._tick_finish(logits)
        finally:
            # if _assign/tick raised mid-flight, drop the in-flight
            # fingerprint launch and evict this submission's cached keys so
            # a retry (or the next submit_all) starts clean -- otherwise
            # _pending_keys/_req_key_cache leak one entry per failed request
            self._pending_keys = None
            for r in requests:
                self._req_key_cache.pop(r.req_id, None)
        return requests

    def tick(self):
        """One lockstep decode step across all active slots.

        SIMPLIFICATION (documented limitation): all slots share one decode
        position (max over slots), so a request assigned at a later tick
        decodes at a shifted absolute position -- fine for the relative
        attention math (its own cache entries carry correct ordering) but
        greedy outputs are not bit-identical to a solo run unless the slot
        joined at tick 0. A production engine threads per-slot positions
        (pos as a (B,) vector) through decode_step; see DESIGN.md §5.
        """
        self._tick_finish(self._tick_launch())

    def _tick_launch(self):
        """Dispatch one decode step (jax async dispatch: returns the
        in-flight logits WITHOUT syncing, so the host can do admission /
        bookkeeping while the device computes)."""
        self.stats["ticks"] += 1
        if self.admission is not None and self.admission.degraded:
            self.stats["degraded_ticks"] += 1
        toks = np.zeros((self.B, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None:
                toks[i, 0] = req.out_tokens[-1]
        pos = int(max(self.slot_pos))  # lockstep position (simple engine)
        logits, self.caches = self._decode(
            self.params, self.caches, jnp.asarray(toks),
            jnp.asarray(pos, jnp.int32))
        return logits

    def _tick_finish(self, logits):
        """Materialize the decode launch (the sync point) and advance slots."""
        logits = np.asarray(logits)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            nxt = int(np.argmax(logits[i]))
            req.out_tokens.append(nxt)
            self.slot_pos[i] += 1
            if len(req.out_tokens) >= req.max_new_tokens or self.slot_pos[i] >= self.S - 1:
                req.done = True
                self.slots[i] = None
