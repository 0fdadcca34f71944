"""Per-request lifecycle tracing + engine step accounting.

The serving literature's instrument set (Orca/vLLM-style): a request's
latency decomposes as queue wait (enqueue -> admit), TTFT (enqueue -> first
SAMPLED token; prompt echo is forced output, not generation), and per-token
decode latency; the engine's health decomposes as step duration and batch
occupancy. ``EngineMetrics`` bundles those instruments from one Registry;
the continuous engine holds it as ``self._obs`` and guards EVERY call site
on ``_obs is not None`` — a disabled engine makes zero registry calls
(the off-the-hot-path acceptance gate, tests/test_obs.py).

Timestamps are ``time.monotonic()`` and live on the Request itself
(runtime/continuous.py stamps them), so the derived observations need no
extra bookkeeping structure.
"""

from __future__ import annotations

from .ledger import STALL_CAUSES, TOKEN_KINDS
from .metrics import (COUNT_BUCKETS, LATENCY_BUCKETS, RATE_BUCKETS, Registry)

# Finer low end than LATENCY_BUCKETS: a fused decode step is sub-ms on a
# small model and tens of ms at 7B — both ends must resolve.
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class EngineMetrics:
    """The continuous engine's instrument bundle (one per engine/registry).

    Creation registers every instrument immediately, so a scrape of a
    freshly started server already exposes the full metric set at zero.
    """

    def __init__(self, registry: Registry):
        self.registry = registry
        h, c, g = registry.histogram, registry.counter, registry.gauge
        self.queue_wait = h(
            "dllama_request_queue_wait_seconds",
            "Time from submit() to slot admission")
        self.ttft = h(
            "dllama_request_ttft_seconds",
            "Time from submit() to the first sampled token")
        self.decode_token = h(
            "dllama_request_decode_token_seconds",
            "Per-sampled-token decode latency, averaged per request",
            buckets=STEP_BUCKETS)
        self.prefill = h(
            "dllama_request_prefill_seconds",
            "Time from slot admission to the first sampled token: the wait "
            "for the running step, the admission prefill's programs on the "
            "device, and the first step (TTFT = queue wait + this)")
        self.tokens_per_s = h(
            "dllama_request_tokens_per_second",
            "Sampled tokens/s over a request's admit->finish window",
            buckets=RATE_BUCKETS)
        self.step_duration = h(
            "dllama_engine_step_duration_seconds",
            "One scheduler iteration around the jitted step (step_once or "
            "a fused step_many chain)", buckets=STEP_BUCKETS)
        self.occupancy = h(
            "dllama_engine_batch_occupancy",
            "Active slots entering each device step", buckets=COUNT_BUCKETS)
        self.active_slots = g(
            "dllama_engine_active_slots", "Active slots right now")
        self.queued = g(
            "dllama_engine_queued_requests", "Requests waiting for a slot")
        # ISSUE-8 canonical queue-depth name: the same value as
        # dllama_engine_queued_requests (kept for dashboard compat), both
        # written through set_queue_depth so they can never diverge
        self.queue_depth = g(
            "dllama_queue_depth",
            "Requests waiting for a slot (canonical SLO-observatory "
            "name; equals dllama_engine_queued_requests)")
        self.generated = c(
            "dllama_generated_tokens_total",
            "Tokens emitted into request outputs (prompt echoes included, "
            "matching the CLI's Generated-tokens accounting)")
        self.steps = c(
            "dllama_engine_steps_total", "Device decode steps executed")
        self.compile_events = c(
            "dllama_engine_compile_events_total",
            "Programs made (compiled, or read from the persistent compile "
            "cache) since the server started: while serving it should "
            "stand still (dllama_program_makes_total names them)")
        self.completed = c(
            "dllama_requests_total", "Requests retired normally")
        self.failed = c(
            "dllama_requests_failed_total",
            "Requests failed by a scheduler error (fail_all)")
        self.cancelled = c(
            "dllama_requests_cancelled_total",
            "Requests retired because the consumer vanished")
        # admission-pressure instruments (ISSUE 8): every reason is
        # pre-registered so a fresh scrape shows the full matrix at zero.
        # pool_dry = paged admission requeued at the queue head; deadlock
        # = the all-slots-starved breaker failed the youngest request;
        # oversized / bad_request = the server refused the request before
        # it ever reached the engine queue.
        self.pauses = c(
            "dllama_slot_pauses_total",
            "Page-starved slot pauses: a slot rode one device dispatch "
            "masked inactive waiting for pool pages to free")
        self._rejected = {
            reason: self.registry.labeled_counter(
                "dllama_admission_rejected_total", {"reason": reason},
                "Requests refused or pushed back at admission, by reason")
            for reason in ("pool_dry", "deadlock", "oversized",
                           "bad_request")}
        # paged-KV instruments (page_size > 0 engines move them; contiguous
        # engines expose them at zero — the scrape surface is layout-
        # invariant, so dashboards survive the knob)
        self.kv_pages_free = g(
            "dllama_kv_pages_free",
            "Free pages in the paged KV pool (0 until a paged engine "
            "allocates)")
        self.prefix_hits = c(
            "dllama_prefix_hits_total",
            "Admissions that mapped >= 1 shared prefix page from the "
            "radix tree (copy-free prefill reuse)")
        self.prefill_saved = c(
            "dllama_prefill_tokens_saved_total",
            "Prefill positions skipped because their pages were shared "
            "from the radix tree")
        # KV-tiering instruments (ISSUE 12): hbm/host/disk tree-page
        # population, promotion/demotion flow, and per-source-tier
        # prefill savings. Pre-registered at zero like the paged series —
        # untiered engines expose the full matrix flat, so dashboards
        # survive the --kv-host-pages/--kv-disk-dir knobs.
        self.tier_pages = {
            tier: registry.labeled_gauge(
                "dllama_kv_tier_pages", {"tier": tier},
                "Radix-tree pages resident per tier of the KV hierarchy "
                "(hbm = device pool, host = pinned host RAM, disk = "
                "CRC-verified segment files)")
            for tier in ("hbm", "host", "disk")}
        self.tier_promotions = c(
            "dllama_tier_promotions_total",
            "Cold prefix pages raised back into the HBM pool on a radix "
            "hit (async upload; the spilled copy is consumed)")
        self.tier_demotions = c(
            "dllama_tier_demotions_total",
            "Cold prefix pages moved down a tier under LRU pressure "
            "(write-behind: HBM->host on pool pressure, host->disk on "
            "host-budget pressure)")
        self.tier_saved = {
            tier: registry.labeled_counter(
                "dllama_prefill_tokens_saved_by_tier_total",
                {"tier": tier},
                "Prefill positions skipped via prefix sharing, by the "
                "SOURCE tier the shared pages lived in at match time — "
                "host/disk rows are recomputes the tier hierarchy "
                "rescued from drop-on-evict")
            for tier in ("hbm", "host", "disk")}
        # span-ring overflow (ISSUE 15 satellite): spans the bounded
        # timeline ring evicted — a /debug/timeline scrape that shows N
        # spans with this counter moving is a TRUNCATED window, not the
        # whole story (the exports carry the same count inline)
        self.spans_dropped = c(
            "dllama_spans_dropped_total",
            "Timeline spans evicted by the SpanTracer ring bound "
            "(exports also carry the count as a 'dropped' field)")
        # crash-safety instruments (ISSUE 9): journal append volume and
        # journal-replayed re-admissions. Pre-registered at zero like the
        # rest — a journal-less engine still exposes them, so dashboards
        # survive the --journal knob.
        self.journal_records = c(
            "dllama_journal_records_total",
            "Write-ahead journal records appended (admit + sampled-token "
            "+ retire lines, runtime/journal.py)")
        self.recoveries = c(
            "dllama_recoveries_total",
            "Requests re-admitted from the journal by "
            "ContinuousEngine.recover after a crash or drain")
        # speculative-decoding instruments (spec_k > 0 engines move them;
        # plain engines expose them at zero — layout-invariant scrape
        # surface, same contract as the paged-KV series above)
        self.spec_proposed = c(
            "dllama_spec_proposed_total",
            "Draft tokens proposed by the n-gram self-drafter and fed to "
            "a verify dispatch (runtime/speculative.py)")
        self.spec_accepted = c(
            "dllama_spec_accepted_total",
            "Draft tokens the verify forward accepted (greedy exact "
            "match, or the rejection-sampling accept at temperature > 0)")
        # routed-expert instruments: an expert model's decode steps move
        # them (models/llama.forward_batch_paged's per-dispatch (L, E)
        # counts); dense engines expose the two totals flat at zero. The
        # per-expert rows appear with the first counted dispatch
        self.moe_pairs = c(
            "dllama_moe_routed_pairs_total",
            "(row, expert) pairs routed in decode dispatches, summed over "
            "layers")
        self.moe_active = c(
            "dllama_moe_active_experts_total",
            "Distinct experts a decode dispatch routed to, summed over "
            "layers and dispatches: the expert tiles a step must read")
        self.moe_local_pairs = c(
            "dllama_moe_local_pairs_total",
            "Routed pairs that landed on an expert this engine holds (all "
            "of them, unless the model file holds a share of the experts)")
        self.moe_slots = c(
            "dllama_moe_slots_total",
            "Live slots of the expert slot kernel in decode dispatches (a "
            "held expert's rows in slots of up to 8), summed over layers")
        self.moe_single_row_slots = c(
            "dllama_moe_single_row_slots_total",
            "Of those, slots that held one row")
        self.moe_diag_slots = c(
            "dllama_moe_diag_slots_total",
            "Of those, slots that took the kernel's block-diagonal body (1 "
            "or 2 live rows on leaves whose block count is a multiple of 8; "
            "fuller slots run the MXU tile)")
        self.dense_diag_steps = c(
            "dllama_dense_diag_steps_total",
            "Landed decode steps whose dense Q40 matmuls took the stacked "
            "block-diagonal body (1 or 2 live rows of a dispatch of up to "
            "8) and not the 8-row MXU tile")
        self.moe_chunk_pairs = c(
            "dllama_moe_chunk_pairs_total",
            "Routed pairs that landed on held experts in admission prefill "
            "chunks, summed over layers")
        self.moe_chunk_slots = c(
            "dllama_moe_chunk_slots_total",
            "Live slots of the expert slot kernel in admission prefill "
            "chunks, at the chunk's rows a slot, summed over layers")
        self.latent_pages = g(
            "dllama_latent_pages_in_use",
            "Pool pages the sequences of a latent-attention model hold "
            "(one plane of latent.width values a position and layer)")
        # a spec with several residual streams (ContinuousStats.hc_streams
        # / hc_sublayers_a_step): fixed by the spec, 0 without streams
        self.hc_streams = g(
            "dllama_hc_streams",
            "Residual streams the model's layers mix (manifold-constrained "
            "hyper-connections; 0: the plain x + F(x))")
        self.hc_sublayers_a_step = g(
            "dllama_hc_sublayers_a_step",
            "Sub-layers a decode step mixes the residual streams around "
            "(two a layer; 0 without streams)")
        self._moe_rows: list = []
        # step_once's run-ahead (ContinuousStats.steps_ahead /
        # rows_dropped_ahead): how often the decode iteration engages
        self.steps_ahead = c(
            "dllama_serve_steps_ahead_total",
            "Decode steps launched on the previous step's picks while "
            "those were still on the device (all rows greedy)")
        self.rows_dropped_ahead = c(
            "dllama_serve_rows_dropped_ahead_total",
            "Rows of steps launched ahead whose result was thrown away: "
            "the row stopped on a token only the landing told, or was "
            "cancelled meanwhile")
        # the admission account (ContinuousStats.book_land): the landing
        # intervals of every dispatch, those of the dispatches that stood
        # behind an admission's programs on the device queue (with the
        # landing before each, which the enqueue can make late), and what
        # the admissions enqueued. Stall = behind_seconds - behind_steps x
        # (land_seconds - behind_seconds) / (steps - behind_steps)
        self.land_seconds = c(
            "dllama_engine_land_seconds_total",
            "Sum of the landing intervals of the dispatches (a step run "
            "ahead: landing to landing): the time the engine was stepping")
        self.land_behind_admit_seconds = c(
            "dllama_engine_land_behind_admit_seconds_total",
            "... of the dispatches that stood behind at least one "
            "admission's programs on the device queue, and of the landing "
            "before each (late by what the next interval lacks)")
        self.lands_behind_admit = c(
            "dllama_engine_lands_behind_admit_total",
            "Device steps of the dispatches booked behind admissions")
        self.admit_prefills = c(
            "dllama_admit_prefills_total",
            "Admissions that enqueued device work (gather or scratch "
            "state, prefill chunks, scatter or insert)")
        self.admit_prefill_chunks = c(
            "dllama_admit_prefill_chunks_total",
            "Admission prefill chunks enqueued")
        self.fetch_wait = c(
            "dllama_engine_fetch_wait_seconds_total",
            "Time the scheduler stood in the blocking read of a "
            "dispatch's results (the device being busy, where it runs "
            "ahead)")
        self.fetch_wait_behind_admit = c(
            "dllama_engine_fetch_wait_behind_admit_seconds_total",
            "... of the dispatches booked behind admissions (the host's "
            "own part of an iteration is read from the others)")
        # a retention spec's state (ContinuousStats.state_bytes /
        # min_normaliser); other engines expose them flat at zero
        self.state_bytes = g(
            "dllama_state_bytes",
            "Resident bytes of the slots' recurrent states (a retention "
            "model's per-sequence memory: fixed, whatever the context)")
        self.retention_min_normaliser = g(
            "dllama_retention_min_normaliser",
            "Smallest normaliser phi(q).z any decode step has read among "
            "its active rows and layers: near zero, a state has decayed "
            "to nothing or a first position was read by cancellation")
        # a hybrid spec's (ContinuousStats.window_bytes and below): its
        # recurrent states are ``dllama_state_bytes``
        self.window_bytes = g(
            "dllama_window_bytes",
            "Resident bytes of the slots' window rings (a hybrid model's "
            "window-attention layers: fixed, whatever the context)")
        self.shared_kv_pages = g(
            "dllama_shared_kv_pages_in_use",
            "Pool pages of a hybrid model's ONE full-attention layer in use")
        self.shared_kv_positions = c(
            "dllama_shared_kv_positions_total",
            "Cached positions the launched decode steps' rows read in ONE "
            "of the layers that read the full layer's K / V, summed")
        self.window_kv_positions = c(
            "dllama_window_kv_positions_total",
            "Ring slots the launched decode steps' rows read in ONE window "
            "layer, summed (min(position + 1, window) a row)")
        self.prompt_positions = c(
            "dllama_prompt_positions_total",
            "Prompt positions a hybrid model admitted")
        self.xdec_positions = c(
            "dllama_xdec_positions_total",
            "... of which ran the cross-decoder (1 a prompt where admission "
            "chunks took the rest)")
        self.ssm_min_decay = g(
            "dllama_ssm_min_decay",
            "Smallest mean decay of the slowest state any active row's "
            "Mamba layer took in a decode step: near zero, a state forgets "
            "everything in one token")
        self._hybrid_seen = [0, 0, 0, 0]
        # an ssd spec's (ContinuousStats.layers_run): a series a layer kind
        self._layers_run: dict = {}
        # a mixer-kinds spec's (ContinuousStats.gate_min and below): its
        # rings, full layers' pages and the positions its decode steps
        # read are the hybrid gauges and counters above
        self.attn_gate_min = g(
            "dllama_attn_gate_min",
            "Smallest per-head output gate any decode step applied over "
            "its layers, active rows and heads: near zero, a head is shut")
        self.attn_gate_mean = g(
            "dllama_attn_gate_mean",
            "Mean per-head output gate over the decode steps so far")
        # cost-ledger / scheduler-census series (ISSUE 16). The closed
        # vocabularies (token kinds, stall causes) pre-register so a
        # fresh scrape shows the full matrix at zero; per-class series
        # auto-create on first sight of a class, with "default" seeded
        # so the family exists from the start (the reject(reason) idiom)
        self.dispatch_tokens = {
            kind: registry.labeled_counter(
                "dllama_dispatch_tokens_total", {"kind": kind},
                "Tokens accounted by the dispatch census, by kind "
                "(decode = sampled, prefill = prompt positions "
                "filled/echoed, spec = draft tokens proposed)")
            for kind in TOKEN_KINDS}
        self.stall_seconds = {
            cause: registry.labeled_counter(
                "dllama_stall_seconds_total", {"cause": cause},
                "Request-attributed stall wall time by cause (pool_dry "
                "= page-starved park, promo_pending = tier promotion "
                "in flight, prefill_hold = admission hold park, "
                "queue_wait = waiting for a slot, handoff_wait = DCN "
                "page shipping)")
            for cause in STALL_CAUSES}
        self._page_seconds: dict = {}
        self.add_page_seconds("default", 0.0)
        self._cost_hists: dict = {}
        self._cost_hist("default")
        self._queue_by_class: dict = {}
        self.set_class_queue_depth({"default": 0})
        self._queue_wait_by_class: dict = {}
        self._class_queue_wait("default")
        # per-scheme collective series, bound by bind_collectives() when
        # the engine runs sharded: [(launch counter, byte counter,
        # launches/step, bytes/step)] — empty (and never touched) at tp=1
        self._collectives: list = []
        # Σ bytes/chip/step of the bound collective schedule — the
        # ledger's ICI pro-ration numerator (0.0 until bind_collectives)
        self.ici_bytes_per_step = 0.0
        self._startup_feed = None   # bind_startup's listener, while bound

    def bind_kv_pool(self, kv_quant: str, pool_bytes: int,
                     n_pages: int) -> None:
        """Register the paged-pool capacity series (ISSUE 11): an info
        gauge naming the KV page quantization in play
        (dllama_kv_quant_info{kv_quant=...} = 1 — the Prometheus *_info
        idiom) plus the pool's GLOBAL logical bytes and per-page bytes,
        so the equal-HBM capacity claim (q8 pages cost ~1/3.8 of f32)
        is provable from a scrape. The byte gauges are whole-pool
        totals across all tp shards (divide by tp for per-device HBM —
        the kv-head axis shards evenly). Called once by paged engines at
        construction; contiguous engines never touch it."""
        self.registry.labeled_gauge(
            "dllama_kv_quant_info", {"kv_quant": kv_quant},
            "KV page quantization in effect (value is always 1; the "
            "label carries the mode)").set(1)
        self.registry.gauge(
            "dllama_kv_page_pool_bytes",
            "Logical bytes of the allocated KV page-pool planes, whole "
            "pool across all tp shards (all layers, K+V, codes+scales "
            "for q8, scrap page included; divide by tp for "
            "per-device)").set(pool_bytes)
        self.registry.gauge(
            "dllama_kv_page_bytes",
            "Logical bytes of ONE physical page across all layers and "
            "tp shards (pool bytes / physical pages)").set(
                pool_bytes // max(n_pages, 1))

    def bind_startup(self) -> None:
        """Copy the process's start-up account (obs/spans) into the
        registry and keep its programs current: the phases as they stood
        at this call (``dllama_startup_seconds``, by ``phase``), and every
        program made so far and, through the account's listener, from now
        on (``dllama_program_makes_total`` and
        ``dllama_program_make_seconds_total``, by ``program`` and ``how``).
        Programs made FROM NOW ON also count in
        ``dllama_engine_compile_events_total``. ``unbind_startup`` ends
        the feed (``InferenceServer.start`` / ``stop``)."""
        from .spans import on_program_made, program_seconds

        def made(program: str, how: str, seconds: float,
                 makes: int = 1) -> None:
            labels = {"program": program, "how": how}
            self.registry.labeled_counter(
                "dllama_program_makes_total", labels,
                "Programs made, by name (the jitted function's) and by "
                "how: compiled, or read from the persistent compile "
                "cache").inc(makes)
            self.registry.labeled_counter(
                "dllama_program_make_seconds_total", labels,
                "Seconds of making them: tracing, lowering and the "
                "compile or cache read").inc(seconds)

        def feed(program: str, how: str, seconds: float) -> None:
            made(program, how, seconds)
            self.compile_events.inc()

        self.unbind_startup()
        self._startup_feed = feed
        account = on_program_made(feed)
        for phase, seconds in account["phases"].items():
            self.registry.labeled_gauge(
                "dllama_startup_seconds", {"phase": phase},
                "Wall seconds of a start-up phase (load, pack, place, "
                "cache, engine: obs/spans.startup_phase), each less the "
                "phases inside it").set(seconds)
        for program, hows in account["programs"].items():
            for how, row in hows.items():
                made(program, how, program_seconds(row), row["makes"])

    def unbind_startup(self) -> None:
        from .spans import off_program_made

        feed, self._startup_feed = self._startup_feed, None
        if feed is not None:
            off_program_made(feed)

    def set_queue_depth(self, n: int) -> None:
        """Write BOTH queue gauges (legacy + canonical) in one place."""
        self.queued.set(n)
        self.queue_depth.set(n)

    def reject(self, reason: str) -> None:
        """Count one admission rejection; unknown reasons get their own
        series on first use (the fixed set above stays visible at
        zero)."""
        counter = self._rejected.get(reason)
        if counter is None:
            counter = self.registry.labeled_counter(
                "dllama_admission_rejected_total", {"reason": reason},
                "Requests refused or pushed back at admission, by reason")
            self._rejected[reason] = counter
        counter.inc()

    def rejected_total(self) -> dict:
        """{reason: count} for /health (zero series included)."""
        return {reason: int(c.value)
                for reason, c in sorted(self._rejected.items())}

    def count_dispatch_tokens(self, kind: str, n: int = 1) -> None:
        self.dispatch_tokens[kind].inc(n)

    def add_stall_seconds(self, cause: str, dt_s: float) -> None:
        if dt_s > 0:
            self.stall_seconds[cause].inc(dt_s)

    def add_page_seconds(self, cls: str, s: float) -> None:
        """Per-SLO-class KV page-seconds counter; classes auto-create
        on first sight (reject(reason) idiom, "default" pre-seeded)."""
        c = self._page_seconds.get(cls)
        if c is None:
            c = self.registry.labeled_counter(
                "dllama_page_seconds_total", {"class": cls},
                "KV page-seconds held, attributed to the owning "
                "request's SLO class (pages x dispatch wall time, "
                "integrated at step granularity)")
            self._page_seconds[cls] = c
        if s > 0:
            c.inc(s)

    def _cost_hist(self, cls: str) -> dict:
        """The per-class request-cost histogram triple (created on
        first sight of the class)."""
        hs = self._cost_hists.get(cls)
        if hs is None:
            lh = self.registry.labeled_histogram
            hs = {
                "dispatch": lh(
                    "dllama_request_cost_dispatch_seconds",
                    {"class": cls},
                    "Per-request share of dispatch wall time (decode "
                    "rows + prefill chunks), observed at close"),
                "page": lh(
                    "dllama_request_cost_page_seconds", {"class": cls},
                    "Per-request KV page-seconds held, observed at "
                    "close"),
                "stall": lh(
                    "dllama_request_cost_stall_seconds", {"class": cls},
                    "Per-request stall wall time summed over causes, "
                    "observed at close"),
            }
            self._cost_hists[cls] = hs
        return hs

    def _class_queue_wait(self, cls: str):
        h = self._queue_wait_by_class.get(cls)
        if h is None:
            h = self.registry.labeled_histogram(
                "dllama_request_queue_wait_by_class_seconds",
                {"class": cls},
                "Time from submit() to slot admission, by SLO class "
                "(head-of-line blocking across classes is visible "
                "here, not in the class-blind aggregate)")
            self._queue_wait_by_class[cls] = h
        return h

    def set_class_queue_depth(self, counts: dict) -> None:
        """Write dllama_queue_depth_by_class{class=...}: every class in
        ``counts`` gets its depth; previously-seen classes absent from
        this snapshot drop to zero (a drained class must read 0, not
        its stale last value)."""
        for cls in self._queue_by_class:
            if cls not in counts:
                self._queue_by_class[cls].set(0)
        for cls, n in counts.items():
            g = self._queue_by_class.get(cls)
            if g is None:
                g = self.registry.labeled_gauge(
                    "dllama_queue_depth_by_class", {"class": cls},
                    "Requests waiting for a slot, by SLO class "
                    "(dllama_queue_depth is the class-blind sum)")
                self._queue_by_class[cls] = g
            g.set(n)

    def observe_request_cost(self, snap: dict) -> None:
        """Fold one CLOSED ledger snapshot into the per-class cost
        histograms + the page-seconds counter."""
        cls = snap.get("class") or "default"
        hs = self._cost_hist(cls)
        hs["dispatch"].observe(snap.get("dispatch_s", 0.0)
                               + snap.get("prefill_s", 0.0))
        hs["page"].observe(snap.get("page_s", 0.0))
        hs["stall"].observe(sum((snap.get("stall_s") or {}).values()))
        self.add_page_seconds(cls, 0.0)  # ensure the class series exists

    def bind_collectives(self, budget, scheme: str, rows: int = 1) -> None:
        """Register the analytic collective budget as labeled series so
        /metrics shows the exact schedule the drift gate checks against
        (ISSUE 5): one {kind, scheme} series pair per budget entry,
        incremented per device step. ``rows`` scales BYTES only — the
        batched forward moves ``rows`` activation rows per collective
        while the launch count stays the per-step schedule."""
        self._collectives = [
            (self.registry.labeled_counter(
                "dllama_ici_collectives_total",
                {"kind": kind, "scheme": scheme},
                "Collective launches, analytic per-step schedule "
                "(comm_stats.tp_collective_budget)"),
             self.registry.labeled_counter(
                "dllama_ici_bytes_total",
                {"kind": kind, "scheme": scheme},
                "Bytes moved per chip by the collective schedule "
                "(ring-accounted, comm_stats)"),
             count, moved_bytes * rows)
            for kind, count, moved_bytes in budget.entries]
        # the ledger pro-rates ICI per active row from this (bytes/chip
        # per device step, whole-batch)
        self.ici_bytes_per_step = float(
            sum(moved_bytes * rows for _, _, moved_bytes in budget.entries))

    def record_step(self, dt_s: float, active: int, steps: int = 1) -> None:
        """One scheduler iteration: ``steps`` device steps (1 for
        step_once, K for a fused chain) over ``active`` slots."""
        self.steps.inc(steps)
        self.step_duration.observe(dt_s)
        self.occupancy.observe(active)
        self.active_slots.set(active)
        for launches, moved, n, b in self._collectives:
            launches.inc(n * steps)
            moved.inc(b * steps)

    def record_land(self, dt_s: float, wait_s: float,
                    steps_behind_admit: int) -> None:
        """One landed dispatch's interval and the part of it spent in the
        blocking read; ``steps_behind_admit`` its device steps if it is
        booked behind admissions (``ContinuousStats.book_land``), else 0."""
        self.land_seconds.inc(dt_s)
        self.fetch_wait.inc(wait_s)
        if steps_behind_admit:
            self.land_behind_admit_seconds.inc(dt_s)
            self.lands_behind_admit.inc(steps_behind_admit)
            self.fetch_wait_behind_admit.inc(wait_s)

    def record_moe(self, counts, held: slice = slice(None),
                   slots: tuple = (0, 0, 0)) -> None:
        """One decode dispatch's (L, E) rows-per-expert counts; ``held``
        the columns of the experts the engine holds; ``slots`` its (live
        slots, one-row slots, block-diagonal slots) as the slot kernel saw
        them."""
        self.moe_pairs.inc(int(counts.sum()))
        self.moe_local_pairs.inc(int(counts[:, held].sum()))
        self.moe_active.inc(int((counts[:, held] > 0).sum()))
        self.moe_slots.inc(slots[0])
        self.moe_single_row_slots.inc(slots[1])
        self.moe_diag_slots.inc(slots[2])
        if not self._moe_rows:
            self._moe_rows = [
                self.registry.labeled_counter(
                    "dllama_moe_expert_rows_total", {"expert": str(e)},
                    "Rows routed to each expert in decode dispatches, "
                    "summed over layers")
                for e in range(counts.shape[1])]
        for ctr, rows in zip(self._moe_rows, counts.sum(axis=0)):
            ctr.inc(int(rows))

    def record_moe_chunk(self, local_pairs: int, slots: int) -> None:
        """One admission prefill chunk of an expert model: the pairs that
        landed on held experts and the live slots they filled."""
        self.moe_chunk_pairs.inc(local_pairs)
        self.moe_chunk_slots.inc(slots)

    def record_hybrid(self, st) -> None:
        """A hybrid model's counters as of a landed step, from the engine's
        ``ContinuousStats`` (the counters advance by what is new)."""
        now = [st.shared_kv_positions, st.window_kv_positions,
               st.prompt_positions, st.xdec_positions]
        for ctr, new, old in zip(
                (self.shared_kv_positions, self.window_kv_positions,
                 self.prompt_positions, self.xdec_positions), now,
                self._hybrid_seen):
            ctr.inc(new - old)
        self._hybrid_seen = now
        self.shared_kv_pages.set(st.shared_kv_pages)
        self.ssm_min_decay.set(st.ssm_min_decay)
        if st.gate_steps:
            self.attn_gate_min.set(st.gate_min)
            self.attn_gate_mean.set(st.gate_mean)

    def record_layers_run(self, kinds: tuple) -> None:
        """One landed decode step of a model whose layer is ONE mixer: its
        layers by kind (``dllama_layers_run_total{kind=...}``)."""
        for kind in kinds:
            ctr = self._layers_run.get(kind)
            if ctr is None:
                ctr = self._layers_run[kind] = self.registry.labeled_counter(
                    "dllama_layers_run_total", {"kind": kind},
                    "Layers the landed decode steps ran, by kind (mamba2, "
                    "full or experts where a layer is ONE mixer; kda or "
                    "latent where delta-rule layers stand beside latent "
                    "attention)")
            ctr.inc()

    def record_retire(self, req, now: float) -> None:
        """Derive the lifecycle histograms at retirement. Cancelled and
        failed requests count in their own counters only — their truncated
        windows would poison the latency distributions."""
        if req.cancelled:
            self.cancelled.inc()
            return
        if req.error is not None:
            self.failed.inc()
            return
        self.completed.inc()
        if req.t_admit and req.t_enqueue:
            self.queue_wait.observe(req.t_admit - req.t_enqueue)
            # the ledger already resolved the billing class through the
            # SLO policy default; fall back only for ledger-less engines
            cls = (getattr(getattr(req, "ledger", None), "slo_class", None)
                   or getattr(req, "slo_class", "") or "default")
            self._class_queue_wait(cls).observe(
                req.t_admit - req.t_enqueue)
        if req.t_first_token and req.t_enqueue:
            self.ttft.observe(req.t_first_token - req.t_enqueue)
        if req.n_sampled > 0 and req.t_first_token:
            span = now - req.t_first_token
            self.decode_token.observe(span / req.n_sampled)
            window = now - (req.t_admit or req.t_enqueue or now)
            if window > 0:
                self.tokens_per_s.observe(req.n_sampled / window)
