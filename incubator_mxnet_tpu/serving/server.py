"""Multi-tenant model server over the kvstore RPC fabric.

One `kvstore.rpc.Server` (threaded, length-prefixed JSON+payload
frames — the same transport the parameter server trusts) fronting any
number of loaded models. Each connection's handler thread BLOCKS on its
request's completion event while the per-model batch worker coalesces
every waiting thread's rows into shared forward steps — that handoff
is what turns N concurrent clients into one MXU-shaped batch.

Request flow:
  client infer/decode  →  rpc.Server (an exhausted `_deadline_ms`
  budget is NACKed before the handler runs — satellite of this plane —
  and a live one is stamped onto the server's monotonic clock)  →
  handler unpacks arrays, reads that deadline  →  ContinuousBatcher
  / DecodeLoop (shape buckets, join-window coalescing, EWMA deadline
  shed)  →  handler wakes, packs the row slice back over the wire.

Multi-tenancy is per-model isolation: a model gets its own batcher
thread, queues, and (for decode) KV-cache slot grid, so one tenant's
queue depth or broken checkpoint never blocks another's forward
progress. Telemetry is enabled on construction by default — per-model
p50/p99 latency, QPS counters, and batch-occupancy histograms are the
product surface here, not an option (`telemetry=False` opts out).
"""

import os
import threading
import time

from ..compilecache import store as _ccstore
from ..kvstore import rpc as _rpc
from ..telemetry import catalog as _cat
from ..telemetry import debugz as _dbz
from ..telemetry import export as _texport
from ..telemetry import flight as _fl
from ..telemetry import metrics as _met
from ..telemetry import tracing as _tr
from .decode import DecodeLoop, DecodeRequest
from .loader import ServedModel, load_served_model
from .scheduler import ContinuousBatcher, Request, ShedError
from .wire import pack_arrays, unpack_arrays

__all__ = ["ModelServer"]


class _Tenant:
    """One loaded model: its ServedModel + running scheduler(s)."""

    def __init__(self, name, served, batcher, decode_loop,
                 directory=None):
        self.name = name
        self.served = served
        self.batcher = batcher
        self.decode_loop = decode_loop
        self.directory = directory      # deploy source for serve.deploy

    @property
    def draining(self):
        return any(s is not None and s.draining
                   for s in (self.batcher, self.decode_loop))

    def stop(self):
        if self.batcher is not None:
            self.batcher.stop()
        if self.decode_loop is not None:
            self.decode_loop.stop()


class ModelServer:
    def __init__(self, host="127.0.0.1", port=0, telemetry=True):
        if telemetry:
            _met.enable()
            # compile accounting is part of the serving product surface:
            # the deploy drill asserts a weight swap costs ZERO compiles
            # by reading mxtpu_jit_compiles_total over serve.metrics
            _cat.install_jax_compile_hook()
        self._models = {}
        self._lock = threading.Lock()
        self._timeout = float(os.environ.get("MXTPU_SERVE_TIMEOUT", "60"))
        self._rpc = _rpc.Server(self._handle, host=host, port=port)
        self.addr = self._rpc.addr

    # ----------------------------------------------------------- lifecycle
    def start(self):
        self._rpc.start()
        _fl.set_identity("serving", 0)
        if _dbz.start_from_env(role="serving") is not None:
            _dbz.set_status("serve_addr", "%s:%s" % self.addr)
            _dbz.set_status("models", lambda: sorted(self._models))
            _dbz.set_status("generations", self.generations)
            _dbz.set_status("compile_cache", _ccstore.statusz_entry)
        return self

    def stop(self):
        self._rpc.stop()
        with self._lock:
            tenants = list(self._models.values())
            self._models = {}
        for t in tenants:
            t.stop()
        _cat.serving_models.set(0)

    # -------------------------------------------------------------- models
    def load(self, name, directory=None, served=None, quantize=None,
             max_batch=None, max_wait_ms=None, buckets=None, slots=None,
             cache_len=None, generation=None):
        """Load a model under `name` from a serving checkpoint directory
        (or an already-built ServedModel) and start its schedulers.
        Unnamed knobs fall back to the MXTPU_SERVE_* env defaults.
        `generation` pins a retained generation instead of the
        directory's GENERATION.json pointer (rollout drills start a
        fleet on a known-old generation this way)."""
        if (directory is None) == (served is None):
            raise ValueError("pass exactly one of directory/served")
        if served is None:
            served = load_served_model(directory, quantize=quantize,
                                       generation=generation)
        elif not isinstance(served, ServedModel):
            raise TypeError("served must be a loader.ServedModel")
        batcher = decode_loop = None
        if served.has_encode:
            batcher = ContinuousBatcher(
                name, served.encode_fn, max_batch=max_batch,
                buckets=buckets, max_wait_ms=max_wait_ms,
                pad_value=served.pad_token).start()
        if served.has_decode:
            n_slots = int(slots if slots is not None else
                          os.environ.get("MXTPU_SERVE_SLOTS", "8"))
            n_len = int(cache_len if cache_len is not None else
                        os.environ.get("MXTPU_SERVE_CACHE_LEN", "512"))
            cache = served.make_cache(n_slots, n_len)
            decode_loop = DecodeLoop(
                name, served.step_fn, cache,
                pad_token=served.pad_token,
                prefill_fn=getattr(served, "prefill_fn", None),
                prefill_chunk=getattr(served, "prefill_chunk",
                                      None)).start()
        tenant = _Tenant(name, served, batcher, decode_loop,
                         directory=directory)
        with self._lock:
            if name in self._models:
                tenant.stop()
                raise ValueError("model %r is already loaded" % name)
            self._models[name] = tenant
            _cat.serving_models.set(len(self._models))
        _cat.serving_generation.set(int(served.generation), model=name)
        return self

    def unload(self, name):
        with self._lock:
            tenant = self._models.pop(name, None)
            _cat.serving_models.set(len(self._models))
        if tenant is None:
            raise KeyError("model %r is not loaded" % name)
        tenant.stop()

    def reset_service_estimates(self, name):
        """Drop a model's EWMA service estimates. The first forwards per
        shape carry XLA compile seconds; warm-start flows replay those
        shapes then call this so deadline sheds track steady-state
        service time instead of compile time."""
        t = self._tenant(name)
        if t.batcher is not None:
            t.batcher.reset_service_estimates()
        if t.decode_loop is not None:
            t.decode_loop.reset_service_estimates()

    def _tenant(self, name):
        with self._lock:
            t = self._models.get(name)
        if t is None:
            raise KeyError("model %r is not loaded (have: %s)"
                           % (name, sorted(self._models)))
        return t

    # ----------------------------------------------------- live deploys
    @staticmethod
    def _drain_timeout():
        return float(os.environ.get("MXTPU_DEPLOY_DRAIN_TIMEOUT_S",
                                    "30"))

    def drain(self, name, timeout=None):
        """Fence `name` for a swap: new requests shed retriable
        DRAINING, in-flight work finishes (bounded). True = quiesced."""
        t = self._tenant(name)
        timeout = self._drain_timeout() if timeout is None \
            else float(timeout)
        _fl.record("deploy.drain", model=name,
                   generation=t.served.generation)
        # rides the caller's trace when the drain RPC was sampled, so a
        # deploy's admission outage shows up on the request timeline
        with _tr.span("deploy.drain", model=name):
            ok = True
            if t.batcher is not None:
                ok = t.batcher.drain(timeout) and ok
            if t.decode_loop is not None:
                ok = t.decode_loop.drain(timeout) and ok
        return ok

    def admit(self, name):
        """Re-open admission on `name` after a drain."""
        t = self._tenant(name)
        if t.batcher is not None:
            t.batcher.admit()
        if t.decode_loop is not None:
            t.decode_loop.admit()
        _fl.record("deploy.admit", model=name,
                   generation=t.served.generation)

    def generations(self):
        """{model: {"generation", "draining"}} — what serve.generation
        returns and the rollout coordinator reads."""
        with self._lock:
            tenants = list(self._models.items())
        return {name: {"generation": int(t.served.generation),
                       "draining": t.draining}
                for name, t in tenants}

    def deploy(self, name, generation=None, directory=None):
        """Live weight push: load the target generation's params, drain
        the model (never swap mid-batch), swap in place against the
        bound executables, re-admit. ``generation=None`` follows the
        directory's generation pointer; ``directory=None`` uses the
        directory the model was loaded from. Deploying the generation
        already live is a no-op. Any failure re-admits the OLD weights
        — a broken deploy degrades to 'nothing happened'."""
        from .loader import load_generation_params, read_generation
        t = self._tenant(name)
        directory = directory or t.directory
        if directory is None:
            raise ValueError("model %r was not loaded from a directory; "
                             "pass an explicit deploy directory" % name)
        if generation is None:
            ptr = read_generation(directory)
            if not ptr:
                raise ValueError("no generation pointer under %r"
                                 % directory)
            generation = ptr["generation"]
        generation, prev = int(generation), int(t.served.generation)
        if generation == prev:
            return {"ok": True, "model": name, "generation": generation,
                    "previous": prev, "noop": True}
        # the params land on host BEFORE the drain so the admission
        # outage is just quiesce + one in-place device copy
        params, _meta = load_generation_params(directory, generation)
        t0 = time.perf_counter()
        _cat.deploy_inflight.set(1)
        _fl.record("deploy.start", model=name, generation=generation,
                   previous=prev)
        try:
            if not self.drain(name):
                raise RuntimeError(
                    "model %r did not quiesce within the drain deadline; "
                    "swap aborted" % name)
            t.served.swap_params(params, generation)
            _fl.record("deploy.swap", model=name, generation=generation,
                       previous=prev)
            _cat.serving_generation.set(generation, model=name)
            _cat.deploy_swaps.inc(model=name, outcome="ok")
        except BaseException:
            _cat.deploy_swaps.inc(model=name, outcome="error")
            _fl.record("deploy.abort", model=name, generation=generation,
                       previous=prev)
            raise
        finally:
            self.admit(name)
            _cat.deploy_inflight.set(0)
            _cat.deploy_seconds.observe(time.perf_counter() - t0,
                                        model=name)
        return {"ok": True, "model": name, "generation": generation,
                "previous": prev}

    # ------------------------------------------------------------- handler
    def _handle(self, meta, payload):
        op = meta.get("op", "")
        if op == "serve.ping":
            with self._lock:
                names = sorted(self._models)
            return {"ok": True, "models": names, "addr": list(self.addr)}, b""
        if op == "serve.models":
            with self._lock:
                tenants = list(self._models.items())
            out = {name: {"family": t.served.family,
                          "config": t.served.config,
                          "quantized": t.served.quantized,
                          "modes": [m for m, on in
                                    (("encode", t.served.has_encode),
                                     ("decode", t.served.has_decode)) if on]}
                   for name, t in tenants}
            return {"models": out}, b""
        if op == "serve.infer":
            return self._infer(meta, payload)
        if op == "serve.decode":
            return self._decode(meta, payload)
        if op == "serve.stats":
            return {"stats": self._stats()}, b""
        if op == "serve.generation":
            return {"generations": self.generations()}, b""
        if op == "serve.drain":
            drained = self.drain(meta.get("model", ""),
                                 timeout=meta.get("timeout"))
            return {"ok": True, "model": meta.get("model", ""),
                    "drained": drained}, b""
        if op == "serve.admit":
            self.admit(meta.get("model", ""))
            return {"ok": True, "model": meta.get("model", "")}, b""
        if op == "serve.deploy":
            return self.deploy(meta.get("model", ""),
                               generation=meta.get("generation"),
                               directory=meta.get("directory")), b""
        if op == "serve.metrics":
            if meta.get("format") == "json":
                return {"format": "json"}, \
                    _texport.render_json().encode("utf-8")
            return {"format": "prom"}, \
                _texport.render_prometheus().encode("utf-8")
        if op == "serve.tracez":
            # journey lookup: a trace_id returns THIS replica's stitched
            # timeline for it (exemplars and flight events carry the
            # ids to ask with); bare, the most recent sampled spans
            tid = meta.get("trace_id")
            if tid is not None:
                return {"trace_id": tid, "timeline":
                        _tr.build_timeline(_tr.spans_for_trace(tid),
                                           trace_id=tid)}, b""
            n = int(meta.get("limit", 256))
            return {"spans": _tr.recent_spans(n)}, b""
        raise ValueError("unknown serving op %r" % op)

    @staticmethod
    def _mono_deadline(meta):
        """Clients send a RELATIVE `_deadline_ms` budget which the rpc
        server converts to `_deadline_mono` (its own monotonic clock) the
        moment the frame is read — scheduling never trusts client wall
        time, so clock skew cannot shed a valid request. A legacy
        absolute `_deadline` (unix seconds) still works via
        remaining-budget conversion, with skew exposure."""
        mono = meta.get("_deadline_mono")
        if mono is not None:
            return float(mono)
        dl = meta.get("_deadline")
        if dl is None:
            return None
        return time.monotonic() + (float(dl) - time.time())

    def _wait(self, req, name):
        timeout = self._timeout
        if req.deadline is not None:
            timeout = min(timeout,
                          max(req.deadline - time.monotonic(), 0.0) + 5.0)
        try:
            result = req.wait(timeout)
        except ShedError as e:
            # the scheduler's _shed already put the flight event on the
            # ring (with request id + trace id) — no second record here
            return self._shed_reply(e), b""
        except TimeoutError as e:
            # Nobody will read a late reply: cancel so the schedulers
            # drop the request instead of holding its queue entry or
            # decode slot. Losing the cancel race means it settled at
            # the buzzer — deliver that outcome instead.
            if req.cancel("handler timed out after %.1fs" % timeout):
                _cat.serving_requests.inc(model=name, status="error")
                return {"error": "Timeout: %s" % e}, b""
            try:
                result = req.wait(0)
            except ShedError as e2:
                return self._shed_reply(e2), b""
        manifest, out_payload = pack_arrays(result)
        return {"ok": True, "arrays": manifest}, out_payload

    @staticmethod
    def _shed_reply(e):
        """Wire shape of a shed: "draining" is a RETRIABLE status (the
        client retries another replica / after backoff), overload is
        load-shedding, everything else is a deadline story."""
        return {"error": str(e), "shed": e.stage,
                "draining": e.stage == "draining",
                "deadline_exceeded": e.stage not in ("overload",
                                                     "draining")}

    def _infer(self, meta, payload):
        name = meta.get("model", "")
        tenant = self._tenant(name)
        if tenant.batcher is None:
            raise ValueError("model %r has no encode path" % name)
        arrays = unpack_arrays(meta.get("arrays", []), payload)
        req = Request(name, arrays, deadline=self._mono_deadline(meta))
        tenant.batcher.submit(req)
        return self._wait(req, name)

    def _decode(self, meta, payload):
        name = meta.get("model", "")
        tenant = self._tenant(name)
        if tenant.decode_loop is None:
            raise ValueError("model %r has no decode path" % name)
        arrays = unpack_arrays(meta.get("arrays", []), payload)
        if "tokens" not in arrays:
            raise ValueError("decode needs a 'tokens' prompt array")
        req = DecodeRequest(
            name, arrays["tokens"],
            max_new_tokens=int(meta.get("max_new_tokens", 16)),
            eos_id=meta.get("eos_id"),
            deadline=self._mono_deadline(meta))
        tenant.decode_loop.submit(req)
        return self._wait(req, name)

    def _stats(self):
        """Per-model scheduler state + the latency quantiles the SLO
        dashboards read (p50/p99 straight from the exported histogram)."""
        with self._lock:
            tenants = list(self._models.items())
        out = {}
        for name, t in tenants:
            ent = {"family": t.served.family,
                   "generation": int(t.served.generation)}
            if t.batcher is not None:
                ent["batch"] = t.batcher.stats()
            if t.decode_loop is not None:
                ent["decode"] = t.decode_loop.stats()
            for q, key in ((0.5, "p50_s"), (0.99, "p99_s")):
                v = _cat.serving_request_seconds.quantile(q, model=name)
                if v is not None:
                    ent[key] = round(v, 6)
            occ = _cat.serving_batch_occupancy
            n = occ.count(model=name)
            if n:
                ent["mean_batch_occupancy"] = round(
                    occ.sum(model=name) / n, 3)
            ent["requests"] = {
                s: _cat.serving_requests.value(model=name, status=s)
                for s in ("ok", "shed", "error")}
            out[name] = ent
        return out
