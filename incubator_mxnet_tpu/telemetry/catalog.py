"""Central catalog of framework metric instruments.

Every instrumented layer (kvstore/rpc.py, kvstore/dist.py,
parallel/trainer.py, gluon/data/dataloader.py, utils/checkpoint.py,
utils/failpoints.py) imports its instruments from here so the full
metric surface is one greppable list — docs/OBSERVABILITY.md mirrors
this catalog.

All instruments are registered at import; registration is cheap and a
registered-but-disabled instrument never mutates (see metrics.py).
"""

import contextlib
import threading

from . import metrics as _m

# -- RPC transport (kvstore/rpc.py) ----------------------------------
rpc_bytes_sent = _m.counter(
    "mxtpu_rpc_bytes_sent_total", "Wire bytes written by send_msg")
rpc_bytes_received = _m.counter(
    "mxtpu_rpc_bytes_received_total", "Wire bytes read by recv_msg")
rpc_client_requests = _m.counter(
    "mxtpu_rpc_client_requests_total",
    "Client RPCs by op and status (ok|error)")
rpc_client_seconds = _m.histogram(
    "mxtpu_rpc_client_seconds", "Client RPC round-trip latency by op")
rpc_retries = _m.counter(
    "mxtpu_rpc_retries_total", "call_idempotent retry attempts by op")
rpc_reconnects = _m.counter(
    "mxtpu_rpc_reconnects_total", "Connection re-establishments after loss")
rpc_server_requests = _m.counter(
    "mxtpu_rpc_server_requests_total",
    "Server-handled RPCs by op and status (ok|error)")
rpc_server_seconds = _m.histogram(
    "mxtpu_rpc_server_seconds", "Server handler latency by op")
rpc_dedup_hits = _m.counter(
    "mxtpu_rpc_dedup_hits_total",
    "Idempotent requests answered from the server DedupCache")
rpc_deadline_dropped = _m.counter(
    "mxtpu_rpc_deadline_dropped_total",
    "Requests NACKed by Server because their _deadline expired before "
    "the handler ran, by op")

# -- dist kvstore (kvstore/dist.py) ----------------------------------
kvstore_pushes = _m.counter(
    "mxtpu_kvstore_pushes_total", "KVStoreDist.push calls by key")
kvstore_pulls = _m.counter(
    "mxtpu_kvstore_pulls_total", "KVStoreDist.pull calls by key")
kvstore_push_bytes = _m.counter(
    "mxtpu_kvstore_push_bytes_total", "Payload bytes pushed to servers")
kvstore_pull_bytes = _m.counter(
    "mxtpu_kvstore_pull_bytes_total", "Payload bytes pulled from servers")

# -- elastic membership (kvstore/dist_server.py, kvstore/dist.py) ----
membership_epoch = _m.gauge(
    "mxtpu_membership_epoch",
    "Current epoch of the scheduler's membership view (advances on every "
    "worker join, graceful departure, or heartbeat eviction)")
membership_quorum = _m.gauge(
    "mxtpu_membership_quorum",
    "Worker count of the current membership epoch — the barrier and "
    "sync-round completion quorum under MXTPU_ELASTIC=1")
membership_joins = _m.counter(
    "mxtpu_membership_joins_total",
    "Workers that joined the membership (initial registration and "
    "mid-training elastic joins)")
membership_departures = _m.counter(
    "mxtpu_membership_departures_total",
    "Graceful worker departures (bye) that shrank the membership")
membership_evictions = _m.counter(
    "mxtpu_membership_evictions_total",
    "Workers evicted from the membership after missing heartbeats past "
    "MXTPU_PS_DEAD_TIMEOUT")
bootstrap_bytes = _m.histogram(
    "mxtpu_bootstrap_bytes",
    "Parameter bytes a joining worker pulled from the servers to enter "
    "the sync round",
    buckets=(1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9))
bootstrap_seconds = _m.histogram(
    "mxtpu_bootstrap_seconds",
    "Wall time of a joining worker's parameter bootstrap")

# -- trainer (parallel/trainer.py) -----------------------------------
trainer_steps = _m.counter(
    "mxtpu_trainer_steps_total",
    "Optimizer steps by zero/pipeline mode labels")
trainer_step_seconds = _m.histogram(
    "mxtpu_trainer_step_seconds", "ShardedTrainer.step wall time")
trainer_samples = _m.counter(
    "mxtpu_trainer_samples_total",
    "Leading-dim samples consumed by step/step_scan (tokens/sec numerator)")
trainer_overlap_pct = _m.gauge(
    "mxtpu_trainer_overlap_pct",
    "Percent of PS gradient-sync time hidden behind compute/compression "
    "by the bucketed push_pull pipeline (100 = fully overlapped, 0 = "
    "serial); written by kvstore/dist.py each bucketed step")
optim_fused_launches = _m.counter(
    "mxtpu_optim_fused_launches_total",
    "Fused multi-tensor optimizer launches (one per dtype/hyperparam "
    "group per step) that replaced a per-param update loop")
jit_compiles = _m.counter(
    "mxtpu_jit_compiles_total",
    "XLA backend_compile events observed via jax.monitoring, by where "
    "(trainer|serving|warmup|other) — the compile region sets the label "
    "via compiling()")
jit_compile_seconds = _m.counter(
    "mxtpu_jit_compile_seconds_total",
    "Cumulative XLA backend_compile seconds via jax.monitoring, by where")

# -- data pipeline (gluon/data/dataloader.py) ------------------------
dataloader_batches = _m.counter(
    "mxtpu_dataloader_batches_total", "Batches yielded by DataLoader")
dataloader_wait_seconds = _m.histogram(
    "mxtpu_dataloader_batch_wait_seconds",
    "Time the consumer blocked waiting for the next batch")
dataloader_worker_respawns = _m.counter(
    "mxtpu_dataloader_worker_respawns_total",
    "Pool worker processes replaced after dying mid-epoch")
dataloader_shm_fallbacks = _m.counter(
    "mxtpu_dataloader_shm_fallbacks_total",
    "Batches that fell back from the shm ring to pipe transport")

# -- checkpoint (utils/checkpoint.py) --------------------------------
checkpoint_saves = _m.counter(
    "mxtpu_checkpoint_saves_total", "Checkpoint writes by status (ok|error)")
checkpoint_save_seconds = _m.histogram(
    "mxtpu_checkpoint_save_seconds", "Checkpoint serialize+publish latency")
checkpoint_restores = _m.counter(
    "mxtpu_checkpoint_restores_total",
    "Checkpoint restore attempts by status (ok|error)")
checkpoint_restore_seconds = _m.histogram(
    "mxtpu_checkpoint_restore_seconds", "Checkpoint restore latency")

# -- fault injection (utils/failpoints.py) ---------------------------
failpoints_triggered = _m.counter(
    "mxtpu_failpoints_triggered_total", "Failpoint firings by name")

# -- resilience (resilience/, recordio.py) ---------------------------
guard_skipped_steps = _m.counter(
    "mxtpu_guard_skipped_steps_total",
    "Optimizer updates skipped by the numeric guard (non-finite "
    "loss/grad-norm)")
guard_loss_scale = _m.gauge(
    "mxtpu_guard_loss_scale", "Current dynamic loss scale")
guard_rollbacks = _m.counter(
    "mxtpu_guard_rollbacks_total",
    "Last-good rewinds by source (ring|checkpoint)")
rollback_snapshots = _m.counter(
    "mxtpu_rollback_snapshots_total",
    "Device-state snapshots taken into the rollback ring")
watchdog_fires = _m.counter(
    "mxtpu_watchdog_fires_total", "Watchdog deadline expiries by phase")
recordio_resyncs = _m.counter(
    "mxtpu_recordio_resyncs_total",
    "Corrupt-region skips where the reader resynced to the next magic, "
    "by shard uri")
recordio_quarantined_bytes = _m.counter(
    "mxtpu_recordio_quarantined_bytes_total",
    "Bytes skipped over while resyncing past corrupt RecordIO regions, "
    "by shard uri")


# -- streaming data plane (io/stream/) -------------------------------
stream_batches_served = _m.counter(
    "mxtpu_stream_batches_served_total",
    "Batches a data worker decoded, collated and shipped")
stream_records_served = _m.counter(
    "mxtpu_stream_records_served_total",
    "Records inside the batches a data worker shipped")
stream_batches_fetched = _m.counter(
    "mxtpu_stream_batches_fetched_total",
    "Batches a stream client received (trainer side)")
stream_fetch_retries = _m.counter(
    "mxtpu_stream_fetch_retries_total",
    "Client fetch attempts re-routed after a worker failure or a stale "
    "assignment")
stream_shard_reassignments = _m.counter(
    "mxtpu_stream_shard_reassignments_total",
    "Shards whose rendezvous owner changed on a registry version bump "
    "(worker join/eviction/quarantine)")
stream_quarantined_shards = _m.counter(
    "mxtpu_stream_quarantined_shards_total",
    "Shards the registry quarantined after corruption reports, by uri")
stream_workers = _m.gauge(
    "mxtpu_stream_workers",
    "Data workers currently registered with the stream coordinator")
stream_shards = _m.gauge(
    "mxtpu_stream_shards",
    "Non-quarantined shards the stream coordinator is distributing")
stream_window_records = _m.gauge(
    "mxtpu_stream_window_records",
    "Decoded records resident in a data worker's shuffle-window cache")
stream_client_wait_seconds = _m.histogram(
    "mxtpu_stream_client_wait_seconds",
    "Stream client time-to-batch including failover retries (the remote "
    "analogue of dataloader_batch_wait)")
stream_prefetch_depth = _m.gauge(
    "mxtpu_stream_prefetch_depth",
    "Device batches currently parked in the DevicePrefetcher queue")


# -- serving plane (serving/) ----------------------------------------
serving_requests = _m.counter(
    "mxtpu_serving_requests_total",
    "Serving requests by model and status (ok|shed|error)")
serving_request_seconds = _m.histogram(
    "mxtpu_serving_request_seconds",
    "End-to-end admission->completion latency by model "
    "(the per-model p50/p99 source)")
serving_queue_seconds = _m.histogram(
    "mxtpu_serving_queue_seconds",
    "Time a request waited before joining a forward batch, by model")
serving_batch_occupancy = _m.histogram(
    "mxtpu_serving_batch_occupancy",
    "Rows per executed forward batch by model — >1 means concurrent "
    "requests were coalesced (continuous batching is working)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
serving_forward_seconds = _m.histogram(
    "mxtpu_serving_forward_seconds",
    "Forward/decode step wall time by model and shape bucket")
serving_ttft_seconds = _m.histogram(
    "mxtpu_serving_ttft_seconds",
    "Time-to-first-token by model: arrival to first committed decode "
    "token. Dominated by queue wait + prefill, so the edges run finer "
    "than the default latency buckets at the low end and stop at 30s",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0))
serving_tpot_seconds = _m.histogram(
    "mxtpu_serving_tpot_seconds",
    "Time-per-output-token by model: inter-token gap for tokens after "
    "the first. One decode step is sub-millisecond on small models, so "
    "the edges extend down to 50us where the defaults would saturate",
    buckets=(0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
             0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5))
serving_shed = _m.counter(
    "mxtpu_serving_shed_total",
    "Requests shed by model and stage (queue|join|overload|decode|"
    "draining|capacity) — capacity = the paged KV pool was exhausted, "
    "shed-on-pressure rather than a bug")
serving_decode_steps = _m.counter(
    "mxtpu_serving_decode_steps_total",
    "Autoregressive decode steps executed by model")
serving_decode_slots = _m.gauge(
    "mxtpu_serving_decode_slots_in_use",
    "KV-cache slots currently held by live decode sequences, by model")
serving_models = _m.gauge(
    "mxtpu_serving_models_loaded", "Models currently loaded in the server")
serving_generation = _m.gauge(
    "mxtpu_serving_generation",
    "Checkpoint generation currently live in this server, by model — "
    "the rollout coordinator and the deploy_generation_skew rule read "
    "this to see replicas agree after a rolling weight push")
deploy_inflight = _m.gauge(
    "mxtpu_deploy_inflight",
    "1 while a drain->swap->re-admit deploy is running on this server")
deploy_swaps = _m.counter(
    "mxtpu_deploy_swaps_total",
    "Live weight swaps attempted, by model and outcome (ok|error)")
deploy_seconds = _m.histogram(
    "mxtpu_deploy_seconds",
    "Wall time of one live deploy (drain through re-admit), by model — "
    "the admission outage a rolling weight push costs per replica")


# -- generative engine (generate/) -----------------------------------
gen_prefill_seconds = _m.histogram(
    "mxtpu_gen_prefill_seconds",
    "Chunked-prefill wall time per sequence by model (prompt ingestion "
    "before the first decode step; under GenerateEngine from the start of "
    "the round that fed its first chunk to the end of the round that fed "
    "its last)")
gen_prefill_forwards = _m.counter(
    "mxtpu_gen_prefill_forwards_total",
    "Prefill rounds of GenerateEngine by model: one forward each, feeding "
    "up to R prompts their next chunk (R from the chunk's width and the "
    "cache's bytes a position)")
gen_prefill_rows = _m.counter(
    "mxtpu_gen_prefill_rows_total",
    "Prompt chunks the prefill rounds fed, by model (a round's pad rows "
    "not counted): over mxtpu_gen_prefill_forwards_total, the rows a "
    "prefill forward")
gen_decode_seconds = _m.histogram(
    "mxtpu_gen_decode_seconds",
    "Decode-phase wall time per engine step by model (one plain step "
    "or one speculative propose+verify round)")
gen_decode_steps = _m.counter(
    "mxtpu_gen_decode_steps_total",
    "Plain decode steps by model and by where the step's tokens came "
    "from (fed=device: the array the forward before chose them into, "
    "nothing waited for | host: built from the sequences, after a wait "
    "— a call's first step, one after the live rows changed, every "
    "sampled step)")
gen_loop_steps = _m.counter(
    "mxtpu_gen_loop_steps_total",
    "Loop steps run by a looped model's forwards (OuroPagedLM: every "
    "layer applied loop_steps times a token with one set of weights; a "
    "forward counts loop_steps), by model and phase (prefill|decode)")
gen_ssm_state_bytes = _m.counter(
    "mxtpu_gen_ssm_state_bytes_total",
    "Bytes of recurrent state a state-space model's forwards read and "
    "wrote (NemotronHPagedLM: each fed row's ssm state and conv window, "
    "read once and written once a Mamba layer), by model and phase "
    "(prefill|decode)")
gen_ssm_scan_chunks = _m.counter(
    "mxtpu_gen_ssm_scan_chunks_total",
    "Sub-chunks of the chunked (SSD) scan that prefill forwards ran, a "
    "row and Mamba layer each, by model")
gen_ssm_state_resets = _m.counter(
    "mxtpu_gen_ssm_state_resets_total",
    "Slots whose state entries were zeroed after an allocation (one "
    "launch over every state pool before the next forward), by model: "
    "the cache's name")
gen_tokens_committed = _m.counter(
    "mxtpu_gen_tokens_committed_total",
    "Tokens committed to sequences by model and phase (prefill|decode) "
    "— the numerator of tokens/sec")
gen_spec_proposed = _m.counter(
    "mxtpu_gen_spec_proposed_total",
    "Draft tokens proposed to the target model by speculative rounds")
gen_spec_accepted = _m.counter(
    "mxtpu_gen_spec_accepted_total",
    "Draft tokens accepted by target verification (accept-rate "
    "numerator; denominator is gen_spec_proposed)")
gen_block_forwards = _m.counter(
    "mxtpu_gen_block_forwards_total",
    "Forwards of the block-diffusion loop by model, phase (denoise = "
    "a forward that stores nothing | store = the one that commits a "
    "block's K and V) and ahead (true: launched while the forward before "
    "it was still unread, fed its tokens on the device | false: after "
    "every read, a call's first)")
gen_block_positions_committed = _m.counter(
    "mxtpu_gen_block_positions_committed_total",
    "Cache positions committed by block store passes, by model (block "
    "length a row a block; over the block forwards a row it is the "
    "tokens a forward yields)")
moe_routes = _m.counter(
    "mxtpu_moe_routes_total",
    "Token-expert routes computed by the dropless expert layer, summed "
    "over layers, by model and phase (prefill|decode: the forward's; a "
    "block loop's denoising and store forwards are decode) — tokens x "
    "experts a token x layers a forward: none is dropped (of a layer that "
    "holds a share of its experts: the routes on the experts held here)")
moe_experts_hit = _m.counter(
    "mxtpu_moe_experts_hit_total",
    "Distinct experts that got at least one route, summed over layers "
    "and forwards, by model and phase (over layers x forwards: the experts "
    "whose weights a forward reads; a prefill chunk hits most, a decode "
    "step few)")
moe_routes_elsewhere = _m.counter(
    "mxtpu_moe_routes_elsewhere_total",
    "Token-expert routes that an expert layer holding a SHARE of its "
    "experts (``moe_dropless``'s ``held``) left to the chips that hold "
    "the chosen expert, summed over layers, by model: with moe_routes "
    "(then the routes on the experts held here) every route the router "
    "made")
moe_rows_moved = _m.counter(
    "mxtpu_moe_rows_moved_total",
    "Rows that a share-holding expert layer gathered into its first "
    "grouped product (passes x the slots of a pass's tile layout), "
    "summed over layers, by model: over moe_routes, 1 is a layer that "
    "touches its own routes alone, experts / held one that moves every "
    "route")
moe_load_max_over_mean = _m.histogram(
    "mxtpu_moe_load_max_over_mean",
    "The fullest expert's routes over the mean expert's, one "
    "observation a forward (mean over layers), by model — 1 is even "
    "routing",
    buckets=(1, 1.5, 2, 3, 4, 6, 8, 12, 16, 32, 64, 128))
eva_forwards = _m.counter(
    "mxtpu_eva_forwards_total",
    "Forwards over a cache of windows and summaries (EvaPagedLM), by "
    "model and phase (prefill, decode)")
eva_windows_closed = _m.counter(
    "mxtpu_eva_windows_closed_total",
    "Windows closed: a slot's window group reached its rows, its chunks "
    "were pooled into summaries and the window restarted in place, by "
    "model and phase")
eva_summary_rows_written = _m.counter(
    "mxtpu_eva_summary_rows_written_total",
    "Rows committed to the summary group by closings (window // chunk a "
    "closing), by model and phase")
eva_window_rows_read = _m.counter(
    "mxtpu_eva_window_rows_read_total",
    "Exact key/value rows read a layer: a forward's live window rows and "
    "its own chunk, a closing's whole window, by model and phase")
eva_summary_rows_read = _m.counter(
    "mxtpu_eva_summary_rows_read_total",
    "Summary rows read a layer by forwards, by model and phase")
eva_positions = _m.counter(
    "mxtpu_eva_positions_total",
    "Context lengths after the chunk, summed over the rows of every "
    "forward: what a cache that kept every row would read, by model and "
    "phase")
mla_absorbed_forwards = _m.counter(
    "mxtpu_mla_absorbed_forwards_total",
    "Forwards over a latent cache that ran the absorbed attention path "
    "(a chunk of one position: a decode step reads the cached rows "
    "alone), by model")
mla_expanded_forwards = _m.counter(
    "mxtpu_mla_expanded_forwards_total",
    "Forwards over a latent cache that ran the expanded attention path "
    "(a wider chunk: prefill up-projects rows to per-head keys and "
    "values), by model")
mla_expanded_kernel_forwards = _m.counter(
    "mxtpu_mla_expanded_kernel_forwards_total",
    "Expanded forwards whose attention was the paged_latent_prefill "
    "launch (a TPU, a cache of whole tiles, whole-lane widths); the rest "
    "took the lax path, by model")
mla_expanded_rows = _m.counter(
    "mxtpu_mla_expanded_rows_total",
    "Cached latent rows that expanded forwards up-projected AGAIN (the "
    "committed lengths of their sequences, summed): the price of "
    "prefilling in chunks, some L^2 / 2c a prompt of L in chunks of c, "
    "by model")
mla_absorbed_rows_live = _m.counter(
    "mxtpu_mla_absorbed_rows_live_total",
    "Cached latent rows that absorbed forwards had to read (the "
    "committed lengths of their sequences, summed; a layer's), by model")
mla_absorbed_rows_read = _m.counter(
    "mxtpu_mla_absorbed_rows_read_total",
    "Cached latent rows that a layer's absorbed walk fetched for them: "
    "each sequence's own blocks under the paged_latent_decode kernel, "
    "every sequence's tiles up to the longest one's on the lax path; "
    "read / live is the walk's waste, by model")
gen_kv_blocks_in_use = _m.gauge(
    "mxtpu_gen_kv_blocks_in_use",
    "Paged-KV pool blocks currently mapped into live slot block tables")
gen_kv_blocks_free = _m.gauge(
    "mxtpu_gen_kv_blocks_free",
    "Paged-KV pool blocks on the free list (allocation headroom)")
gen_kv_fragmentation = _m.gauge(
    "mxtpu_gen_kv_fragmentation",
    "Unused fraction of mapped paged-KV block capacity "
    "(1 - filled_positions / (blocks_in_use * block_size)); high values "
    "mean many ragged last blocks")
gen_kv_free_fraction = _m.gauge(
    "mxtpu_gen_kv_free_fraction",
    "Free fraction of the paged-KV pool (blocks_free / num_blocks) — "
    "the kv_pool_pressure WARN signal and the autoscaler's headroom "
    "input, by pool name")
gen_kv_blocks_in_use_peak = _m.gauge(
    "mxtpu_gen_kv_blocks_in_use_peak",
    "Pool-lifetime high watermark of mapped paged-KV blocks, by pool "
    "name — how close this pool has ever come to exhaustion")
gen_kv_pool_exhausted = _m.counter(
    "mxtpu_gen_kv_pool_exhausted_total",
    "KVPoolExhausted raises (an append found no free block), by pool "
    "name — the kv_pool_pressure PAGE signal; shed-on-pressure is this "
    "counter moving, a bug is this counter moving with free blocks left")


# -- observability plane (tracing ring, flight, debugz, costs) --------
telemetry_spans_dropped = _m.counter(
    "mxtpu_telemetry_spans_dropped_total",
    "Finished trace spans evicted from the bounded retention ring "
    "(MXTPU_TRACE_MAX_SPANS) to admit newer ones")
flight_events = _m.counter(
    "mxtpu_flight_events_total",
    "Flight-recorder events recorded, by event type")
debugz_requests = _m.counter(
    "mxtpu_debugz_requests_total",
    "Debugz HTTP requests served, by path and status")
lockdep_violations = _m.counter(
    "mxtpu_lockdep_violations_total",
    "Runtime lockdep witness violations by kind (order = lock-order "
    "cycle observed across threads, blocking = lock held across a "
    "blocking operation); see telemetry/lockdep.py")
model_flops_per_exec = _m.gauge(
    "mxtpu_model_flops_per_executable",
    "Static XLA cost-analysis FLOPs for one run of the named executable")
model_bytes_per_exec = _m.gauge(
    "mxtpu_model_bytes_per_executable",
    "Static XLA cost-analysis bytes accessed for one run of the named "
    "executable")
model_achieved_tflops = _m.gauge(
    "mxtpu_model_achieved_tflops",
    "Achieved TFLOP/s over the last observed execution of the named "
    "executable")
model_flops_utilization = _m.gauge(
    "mxtpu_model_flops_utilization",
    "Achieved FLOP/s as a fraction of the MXTPU_PEAK_TFLOPS roofline "
    "(MFU) for the named executable")
model_tokens_per_sec = _m.gauge(
    "mxtpu_model_tokens_per_sec",
    "Samples/tokens consumed per second by the named executable")


# -- device-memory plane (telemetry/memz.py) -------------------------
mem_device_bytes_in_use = _m.gauge(
    "mxtpu_mem_device_bytes_in_use",
    "Device memory currently allocated, by device — from the runtime "
    "allocator (device.memory_stats) or the live_arrays fallback on "
    "backends without one")
mem_device_bytes_limit = _m.gauge(
    "mxtpu_mem_device_bytes_limit",
    "Device memory capacity visible to the allocator, by device (HBM "
    "bytes on TPU/GPU; absent on CPU)")
mem_device_peak_bytes = _m.gauge(
    "mxtpu_mem_device_peak_bytes",
    "Allocator-reported peak bytes in use since process start, by device")
mem_hbm_used_fraction = _m.gauge(
    "mxtpu_mem_hbm_used_fraction",
    "bytes_in_use / bytes_limit, by device — the mxtop HBM%% column "
    "and the first thing to look at before an OOM")
mem_host_rss_bytes = _m.gauge(
    "mxtpu_mem_host_rss_bytes",
    "Host-process resident set size (the Python side of the memory "
    "story: numpy staging buffers, executables, the framework itself)")
mem_watermark_bytes = _m.gauge(
    "mxtpu_mem_watermark_bytes",
    "Process-lifetime memory high watermark, by scope "
    "(device:<name> | host_rss)")
mem_program_bytes = _m.gauge(
    "mxtpu_mem_program_bytes",
    "Static per-program memory footprint from compiled.memory_analysis, "
    "by program name and kind (argument|output|temp|generated_code|"
    "total) — captured at the aot.cached_compile seam on the SAME "
    "executable the step runs")
oom_events = _m.counter(
    "mxtpu_oom_events_total",
    "Out-of-memory observations by kind (kv_pool = paged pool "
    "exhausted, resource_exhausted = XLA RESOURCE_EXHAUSTED) — each "
    "one left an oom.* flight event and, with MXTPU_MEM_EXPORT set, "
    "a post-mortem dump")


# -- persistent compile cache (compilecache/) ------------------------
compile_cache_hits = _m.counter(
    "mxtpu_compile_cache_hits_total",
    "Executables served from the persistent compile cache instead of a "
    "fresh XLA compile, by where")
compile_cache_misses = _m.counter(
    "mxtpu_compile_cache_misses_total",
    "Cache lookups that fell through to a fresh XLA compile, by where")
compile_cache_seconds_saved = _m.counter(
    "mxtpu_compile_cache_seconds_saved_total",
    "Cumulative compile seconds avoided by cache hits (each entry "
    "remembers what its original compile cost)")
compile_cache_errors = _m.counter(
    "mxtpu_compile_cache_errors_total",
    "Cache entries that could not be used, by kind (corrupt|io|"
    "serialize|deserialize) — every one falls back to a fresh compile")
compile_cache_evictions = _m.counter(
    "mxtpu_compile_cache_evictions_total",
    "Entries removed by the MXTPU_COMPILE_CACHE_MAX_MB LRU cap")
compile_cache_entries = _m.gauge(
    "mxtpu_compile_cache_entries",
    "Entries resident in the persistent compile cache directory")
compile_cache_bytes = _m.gauge(
    "mxtpu_compile_cache_bytes",
    "Bytes resident in the persistent compile cache directory")
aot_executables_imported = _m.counter(
    "mxtpu_aot_executables_imported_total",
    "Serialized executables deserialized from a checkpoint's "
    "executables section, by where")


# -- health plane (telemetry/history.py, telemetry/health.py) --------
scrape_errors = _m.counter(
    "mxtpu_scrape_errors_total",
    "Fleet-scrape member fetches that failed (dead/unreachable member), "
    "by member role:rank — aggregate.scrape() records the gap instead "
    "of raising mid-walk")
history_series = _m.gauge(
    "mxtpu_history_series",
    "Distinct (metric, label-key) series retained in the local "
    "MetricHistory ring")
history_series_dropped = _m.counter(
    "mxtpu_history_series_dropped_total",
    "New series rejected because the history held MXTPU_HISTORY_MAX_SERIES")
health_level = _m.gauge(
    "mxtpu_health_level",
    "Current hysteresis-filtered level per health rule "
    "(0=OK, 1=WARN, 2=PAGE)")
health_transitions = _m.counter(
    "mxtpu_health_transitions_total",
    "Health-rule level transitions, by rule and destination level")
health_evaluations = _m.counter(
    "mxtpu_health_evaluations_total",
    "HealthEvaluator.evaluate passes completed")


def default_health_rules():
    """The stock SLO rule pack, as declarative specs for
    ``health.make_rule``.  Budgets/windows are env-tunable so a drill
    (or an impatient operator) can compress the SRE-textbook windows;
    see docs/ENV_VARS.md.  Returned fresh each call — mutate freely."""
    import os

    def _f(name, default):
        try:
            return float(os.environ.get(name, "") or default)
        except ValueError:
            return default

    fast = _f("MXTPU_HEALTH_FAST_WINDOW", 300.0)
    slow = _f("MXTPU_HEALTH_SLOW_WINDOW", 3600.0)
    return [
        # Google-SRE multiwindow burn rates: PAGE only when both the
        # fast window (still burning NOW) and the slow window (enough
        # budget already spent) agree.
        {"type": "burn_rate", "name": "serving_shed_burn",
         "numerator": "mxtpu_serving_shed_total",
         "denominator": "mxtpu_serving_requests_total",
         "budget": _f("MXTPU_HEALTH_SHED_BUDGET", 0.01),
         "fast_window": fast, "slow_window": slow,
         "warn_burn": 2.0, "page_burn": 10.0},
        {"type": "burn_rate", "name": "rpc_retry_burn",
         "numerator": "mxtpu_rpc_retries_total",
         "denominator": "mxtpu_rpc_client_requests_total",
         "budget": _f("MXTPU_HEALTH_RETRY_BUDGET", 0.01),
         "fast_window": fast, "slow_window": slow,
         "warn_burn": 2.0, "page_burn": 10.0},
        {"type": "burn_rate", "name": "compile_cache_error_burn",
         "numerator": "mxtpu_compile_cache_errors_total",
         "denominator": ["mxtpu_compile_cache_hits_total",
                         "mxtpu_compile_cache_misses_total"],
         "budget": _f("MXTPU_HEALTH_CACHE_ERROR_BUDGET", 0.05),
         "fast_window": fast, "slow_window": slow,
         "warn_burn": 2.0, "page_burn": 10.0},
        # Bursts / one-shot badness.
        {"type": "threshold", "name": "guard_skip_burst",
         "metric": "mxtpu_guard_skipped_steps_total", "source": "increase",
         "window": fast, "warn": 1.0,
         "page": _f("MXTPU_HEALTH_GUARD_SKIP_PAGE", 5.0)},
        {"type": "threshold", "name": "watchdog_fired",
         "metric": "mxtpu_watchdog_fires_total", "source": "increase",
         "window": slow, "page": 1.0},
        # Capacity.
        {"type": "threshold", "name": "serving_occupancy_saturation",
         "metric": "mxtpu_serving_batch_occupancy:p99", "source": "latest",
         "warn": _f("MXTPU_HEALTH_OCCUPANCY_WARN", 0.9) *
                 _f("MXTPU_SERVE_MAX_BATCH", 8)},
        # KV-block economy: WARN while any paged pool sustains low free
        # blocks (the autoscaler's scale-up signal), PAGE when appends
        # are actually dying of exhaustion (sessions are being shed).
        {"type": "kv_pool", "name": "kv_pool_pressure",
         "free_warn": _f("MXTPU_HEALTH_KV_POOL_FREE_WARN", 0.10),
         "exhausted_page": _f("MXTPU_HEALTH_KV_POOL_EXHAUSTED_PAGE", 3.0),
         "window": fast,
         "fire_for": int(_f("MXTPU_HEALTH_KV_POOL_FOR", 2))},
        # Fleet consistency: ranks disagreeing on the membership epoch
        # means someone is acting on a stale view.
        {"type": "threshold", "name": "membership_epoch_stale",
         "metric": "mxtpu_membership_epoch", "source": "latest",
         "agg": "spread", "warn": 1.0, "fire_for": 3},
        # Replicas disagreeing on the served generation for longer than
        # the bake window: a rollout stalled mid-walk or half rolled
        # back. Transient spread during a healthy walk is expected —
        # fire_for rides it out.
        {"type": "threshold", "name": "deploy_generation_skew",
         "metric": "mxtpu_serving_generation", "source": "latest",
         "agg": "spread", "warn": 1.0,
         "fire_for": int(_f("MXTPU_HEALTH_GENERATION_SKEW_FOR", 3))},
        # Liveness + stragglers.
        {"type": "absence", "name": "member_absent",
         "for_seconds": _f("MXTPU_HEALTH_ABSENCE_SECONDS", 15.0)},
        {"type": "skew", "name": "step_time_straggler",
         "metric": "mxtpu_trainer_step_seconds:p99",
         "warn_factor": _f("MXTPU_HEALTH_SKEW_WARN", 2.0),
         "page_factor": _f("MXTPU_HEALTH_SKEW_PAGE", 4.0)},
        {"type": "skew", "name": "batch_wait_straggler",
         "metric": "mxtpu_dataloader_batch_wait_seconds:p99",
         "warn_factor": _f("MXTPU_HEALTH_SKEW_WARN", 2.0),
         "page_factor": _f("MXTPU_HEALTH_SKEW_PAGE", 4.0)},
    ]


# -- jax compile hook ------------------------------------------------
# jax.monitoring calls duration listeners for every instrumented event;
# we fold the XLA backend-compile ones into the trainer_jit_* counters.
# Installed once (ShardedTrainer.__init__ calls this); the listener
# itself is gated by the metrics enabled flag via Counter.inc.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_hook_lock = threading.Lock()
_hook_state = {"installed": False}
_compile_ctx = threading.local()


@contextlib.contextmanager
def compiling(where):
    """Label XLA compiles fired inside the region: backend_compile events
    observed by the jax.monitoring hook while this context is active are
    counted under ``mxtpu_jit_compiles_total{where=...}``. Nestable; events
    outside any region fall under where="other"."""
    prev = getattr(_compile_ctx, "where", None)
    _compile_ctx.where = where
    try:
        yield
    finally:
        _compile_ctx.where = prev


def compile_events(where=None):
    """Current backend_compile event count — ``where=None`` sums every
    label (the process-wide total)."""
    if where is not None:
        return jit_compiles.value(where=where)
    return sum(jit_compiles.snapshot().values())


def install_jax_compile_hook():
    """Register a jax.monitoring listener feeding the mxtpu_jit_* metrics."""
    with _hook_lock:
        if _hook_state["installed"]:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_jax_event_duration)
        except (ImportError, AttributeError):
            return   # jax too old/new for the monitoring API: skip quietly
        _hook_state["installed"] = True


def _on_jax_event_duration(event, duration, **_kw):
    if event == _COMPILE_EVENT:
        where = getattr(_compile_ctx, "where", None) or "other"
        jit_compiles.inc(where=where)
        jit_compile_seconds.inc(duration, where=where)
