"""Continuous-batching serve engine over per-bucket prepared NetworkPlans.

Production conv traffic is ragged (every client sends a different batch
size) and bursty, but FFT-conv efficiency is strongly geometry- and
batch-dependent (fbfft; Zlateski et al. 2018): the fast path is a plan
that was tuned and prepared for its exact padded shape.  This module is
the serving analogue of the paper's plan-once/execute-many NUMA pipeline:

  1. A ``BucketPolicy`` fixes a small set of padded batch shapes
     (powers of two up to ``max_batch``, optionally a few image sizes).
  2. At startup the engine plans (``plan_network``, optionally
     ``backend="tuned"``) and prepares (``NetworkPlan.prepare``) one
     network per bucket — same-geometry buckets dedupe through the
     shared plan and prepared caches — and jit-compiles one executor per
     (replica, bucket).  With ``load_plans=<artifact>`` the whole sweep
     is replaced by rehydrating an AOT plan artifact
     (``repro.conv.export``): zero plan_conv calls, zero kernel
     transforms, zero retraces at startup.  The steady state executes
     only prepared, epilogue-fused plans: zero re-planning, zero
     re-tracing on the hot path.
  3. ``submit`` enqueues requests; ``drain`` packs the FIFO queue into
     bucket batches (a batching-window/timeout knob trades latency for
     occupancy), pads to the bucket, executes on the next replica
     (round-robin), unpads per request, and records per-request latency.
  4. ``report()`` / ``bench_rows()`` emit per-bucket p50/p99,
     occupancy (padding waste) and queue-depth stats in the
     ``BENCH_conv.json`` schema, so CI gates serving SLOs.

Two reference modes exist only to measure what the bucketing buys
(``benchmarks/run.py`` and the CI serve-smoke step A/B them):

  ``mode="pad-max"``   the seed serve loop's strategy: one planned shape,
                       every request padded to ``max_batch``, no
                       coalescing (throughput baseline).
  ``mode="replan"``    plan+prepare+compile for each request's exact
                       batch size on the hot path (p99 baseline).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
import warnings
from typing import Any, Callable, Optional, Sequence


class RequestTooLarge(ValueError):
    """A request exceeds the largest configured bucket."""


# --------------------------------------------------------------------------
# Bucket policy
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """The fixed set of padded batch shapes the engine prepares for.

    ``batch_buckets()`` is powers of two from ``min_batch`` up, with
    ``max_batch`` always included (``max_batch=6`` -> ``(1, 2, 4, 6)``),
    so a request of size b pads to at most 2x its own rows.
    ``image_sizes`` optionally adds a small set of (square) input sizes;
    requests are grouped per image size and never mixed in one batch.
    """
    max_batch: int
    min_batch: int = 1
    image_sizes: tuple = ()

    def __post_init__(self):
        if self.min_batch < 1 or self.max_batch < self.min_batch:
            raise ValueError(
                f"need 1 <= min_batch <= max_batch, got "
                f"min_batch={self.min_batch} max_batch={self.max_batch}")

    def batch_buckets(self) -> tuple:
        out, b = [], 1
        while b < self.max_batch:
            if b >= self.min_batch:
                out.append(b)
            b *= 2
        out.append(self.max_batch)
        return tuple(out)

    def bucket_for(self, n: int, image: Optional[int] = None) -> int:
        """Smallest bucket >= ``n`` rows (``RequestTooLarge`` above
        ``max_batch``); validates ``image`` against ``image_sizes``."""
        if n < 1:
            raise ValueError(f"request batch must be >= 1, got {n}")
        if n > self.max_batch:
            raise RequestTooLarge(
                f"request batch {n} exceeds the largest bucket "
                f"(max_batch={self.max_batch}); split the request or "
                f"raise --max-batch")
        if self.image_sizes and image not in self.image_sizes:
            raise RequestTooLarge(
                f"request image size {image} is not a configured bucket "
                f"(image_sizes={self.image_sizes})")
        for b in self.batch_buckets():
            if b >= n:
                return b
        raise AssertionError("unreachable: max_batch is always a bucket")


# --------------------------------------------------------------------------
# Requests, stats
# --------------------------------------------------------------------------

@dataclasses.dataclass
class _Request:
    rid: int
    x: Any
    t_arrival: float
    image: Optional[int] = None

    @property
    def rows(self) -> int:
        return int(self.x.shape[0])


@dataclasses.dataclass
class _BucketStats:
    latencies_s: list = dataclasses.field(default_factory=list)
    service_s: list = dataclasses.field(default_factory=list)
    n_requests: int = 0
    n_batches: int = 0
    real_rows: int = 0
    padded_rows: int = 0


def _percentile(values: Sequence[float], q: float) -> float:
    """p-th percentile (nearest-rank on the sorted sample; no numpy dep
    on the hot path)."""
    if not values:
        return float("nan")
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


# --------------------------------------------------------------------------
# Synthetic ragged traffic
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TraceRequest:
    t: float                      # arrival offset from trace start (s)
    batch: int
    image: Optional[int] = None


def synthetic_trace(*, n_requests: int, max_batch: int, rate_rps: float,
                    seed: int = 0, image_sizes: tuple = ()) -> tuple:
    """Reproducible ragged Poisson trace: exponential inter-arrivals at
    ``rate_rps``, batch sizes uniform on 1..max_batch (the acceptance
    trace), optional uniform image-size choice."""
    import numpy as np
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate_rps, 1e-9), n_requests)
    t = 0.0
    out = []
    for g in gaps:
        t += float(g)
        img = int(rng.choice(image_sizes)) if image_sizes else None
        out.append(TraceRequest(t=t, batch=int(rng.integers(1,
                                max_batch + 1)), image=img))
    return tuple(out)


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

class ServeEngine:
    """Shape-bucketed dynamic batcher over per-bucket prepared plans.

    Args:
      make_layers: ``make_layers(batch)`` (or ``make_layers(batch,
        image=s)`` when the policy buckets image sizes) returning the
        ``NetworkConv`` sequence for one padded input shape.
      params: layer-name -> kernel array mapping (``NetworkPlan.prepare``
        contract; biases etc. ride via the ``forward`` closure).
      policy: the ``BucketPolicy``.
      forward: ``forward(prepared_net, x) -> y`` executing one padded
        batch (default: chain the layers in order, no epilogue
        operands).  Compiled once per (replica, bucket) at startup.
      replicas: data-parallel copies — one prepared state per replica
        (params are ``device_put`` round-robin onto the visible
        devices), round-robin batch dispatch.
      window_s: batching window — a queued request is flushed once it
        has waited this long even if its bucket is not full (0 = flush
        every drain).
      mode: ``"bucketed"`` (the engine) | ``"pad-max"`` | ``"replan"``
        (reference baselines, see module docstring).
      timing: ``"per-batch"`` synchronizes after every bucket execution
        so per-request latency is real; ``"async"`` only synchronizes at
        ``finish()`` (throughput mode — percentiles then measure
        dispatch, not completion, and are flagged in the report).
      weights_version: forwarded to ``NetworkPlan.prepare`` (a weight
        update is ``update_weights`` = one invalidation sweep per
        bucket, which also drops any loaded plan artifact).
      load_plans: path to an AOT plan artifact (``repro.conv.export``;
        built by ``export_plans`` or ``serve --export-plans``).  Startup
        becomes artifact-load instead of plan+prepare+compile per bucket
        per replica; on any mismatch (device kind, jax version, bucket
        set, weights version) the engine warns and builds live.
      plan_kwargs: shared ``plan_network`` knobs (backend=, mesh=, ...).
    """

    def __init__(self, make_layers: Callable, params: dict, *,
                 policy: BucketPolicy,
                 forward: Optional[Callable] = None,
                 replicas: int = 1, window_s: float = 0.0,
                 mode: str = "bucketed", timing: str = "per-batch",
                 weights_version: Any = 0, collect_results: bool = True,
                 warm: bool = True, clock: Callable = time.monotonic,
                 load_plans: Optional[str] = None,
                 **plan_kwargs):
        if mode not in ("bucketed", "pad-max", "replan"):
            raise ValueError(f"unknown mode {mode!r}")
        if timing not in ("per-batch", "async"):
            raise ValueError(f"unknown timing {timing!r}")
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if load_plans is not None and mode != "bucketed":
            raise ValueError("load_plans requires mode='bucketed'")
        t_startup = time.perf_counter()
        self.policy = policy
        self.mode = mode
        self.timing = timing
        self.replicas = replicas
        self.window_s = float(window_s)
        self.weights_version = weights_version
        self._make_layers = make_layers
        self._forward = forward if forward is not None else _chain_forward
        self._plan_kwargs = dict(plan_kwargs)
        self._clock = clock
        self._collect = collect_results

        self._queue: collections.deque = collections.deque()
        self._rid = itertools.count()
        self._stats: dict = collections.OrderedDict()
        self._replica_batches = [0] * replicas
        self._rr = 0
        self._pending: list = []          # async-mode in-flight batches
        self.results: dict = {}
        self._t_first_submit: Optional[float] = None
        self._t_last_done: Optional[float] = None
        self._queue_depth_max = 0
        self._n_rejected = 0

        self._params = _replica_params(params, replicas)

        self.nets: dict = collections.OrderedDict()
        self._exec: list = [dict() for _ in range(replicas)]
        self._bucket_x: dict = {}
        self.plan_source = "live"
        if mode != "replan":
            batches = (policy.batch_buckets() if mode == "bucketed"
                       else (policy.max_batch,))
            keys = self._bucket_keys(batches)
            if load_plans is not None:
                try:
                    self._load_buckets(load_plans, keys)
                    self.plan_source = "aot"
                except Exception as e:
                    warnings.warn(
                        f"plan artifact {load_plans!r} unusable ({e}); "
                        "falling back to live planning", stacklevel=2)
            if self.plan_source != "aot":
                for key in keys:
                    self._build_bucket(key)
        self._warm_plan_misses: Optional[int] = None
        if warm:
            self.warm()
        self.startup_s = time.perf_counter() - t_startup

    # ---- bucket construction ---------------------------------------------
    def _bucket_keys(self, batches) -> list:
        images = self.policy.image_sizes or (None,)
        return [(b, img) for img in images for b in batches]

    def _layers_for(self, key):
        b, img = key
        if img is None:
            return self._make_layers(b)
        return self._make_layers(b, image=img)

    def _build_bucket(self, key) -> None:
        """Plan + prepare + compile one padded bucket shape on every
        replica.  Same-geometry buckets dedupe through the shared plan
        cache (identical frozen plans) and the prepared cache (identical
        (plan, kernel) keys per replica)."""
        import jax
        from repro.conv.netplan import plan_network
        net = plan_network(self._layers_for(key), **self._plan_kwargs)
        self.nets[key] = net
        self._bucket_x[key] = net[net.layer_names[0]].x_shape
        # the prepared network is a jit argument: its transformed kernels
        # are executable inputs, not constants compiled into each bucket
        fwd = jax.jit(self._forward)
        for r in range(self.replicas):
            prepared = net.prepare(
                self._params[r], weights_version=self.weights_version)
            self._exec[r][key] = functools.partial(fwd, prepared)

    def _load_buckets(self, path: str, keys) -> None:
        """Rehydrate every bucket executor from an AOT plan artifact —
        zero plan_conv calls, zero kernel transforms, zero layer
        retraces.  Any mismatch raises (the constructor catches it and
        builds live): artifact-level incompatibility, a bucket missing
        from the artifact, or a stale ``weights_version``."""
        import jax
        from repro.conv import export as planx
        arts = planx.load_network(path, on_mismatch="error")
        if isinstance(arts, planx.LoadedNetwork):
            arts = {"net": arts}
        fwd = self._forward
        for key in keys:
            label = self._label(*key)
            if label not in arts:
                raise planx.ArtifactMismatch(
                    f"artifact has no bucket {label!r} "
                    f"(has: {sorted(arts)})")
            net = arts[label]
            if net.weights_version != self.weights_version:
                raise planx.ArtifactMismatch(
                    f"artifact weights_version {net.weights_version!r} "
                    f"!= engine weights_version "
                    f"{self.weights_version!r}")
            self._bucket_x[key] = tuple(net.x_shape)
            # Native-executable layers (zero-compile rehydration) cannot
            # be traced through an outer jit — chain them eagerly; each
            # layer IS a compiled XLA module already.  Portable StableHLO
            # fallbacks compose under jit as usual.
            native = any(getattr(lc, "native", False)
                         for lc in net.layers.values())
            for r in range(self.replicas):
                if native:
                    self._exec[r][key] = lambda x, _p=net: fwd(_p, x)
                else:
                    self._exec[r][key] = jax.jit(
                        lambda x, _p=net: fwd(_p, x))

    def export_plans(self, path: str) -> str:
        """AOT-export every bucket's planned+prepared network (replica
        0's params) into one artifact keyed by the current
        ``weights_version`` — the build-once half of fleet cold-start
        (``load_plans=`` / ``serve --load-plans`` is the deploy-many
        half)."""
        if not self.nets:
            raise RuntimeError(
                "export_plans needs a live-planned bucketed engine "
                "(a loaded-artifact engine has no NetworkPlans to "
                "export; rebuild with load_plans=None)")
        from repro.conv import export as planx
        nets = collections.OrderedDict(
            (self._label(b, img), net)
            for (b, img), net in self.nets.items())
        return planx.export_network(
            nets, path, params=self._params[0],
            weights_version=self.weights_version)

    def _executor(self, key, replica):
        ex = self._exec[replica].get(key)
        if ex is None:
            if self.mode != "replan":
                raise AssertionError(f"no executor for bucket {key}")
            # the replan baseline pays plan+prepare+compile here, on the
            # hot path — that cost lands in the request latencies
            self._build_bucket(key)
            ex = self._exec[replica][key]
        return ex

    def warm(self) -> None:
        """Execute one zero batch per (replica, bucket) so every jit
        compile is paid before the first request; snapshots the plan
        cache so ``report()`` can certify zero misses after warmup."""
        import jax
        import jax.numpy as jnp
        from repro.conv.plan import plan_cache_info
        for key in self._exec[0]:
            x = jnp.zeros(self._bucket_x[key], jnp.float32)
            for r in range(self.replicas):
                jax.block_until_ready(self._exec[r][key](x))
        self._warm_plan_misses = plan_cache_info().misses

    def update_weights(self, params: dict, *, weights_version) -> None:
        """Weight update: one invalidation sweep re-preparing every
        bucket on every replica under the new version.  An engine
        started from a plan artifact drops it here (the artifact is
        keyed to the old ``weights_version``) and re-plans live —
        export_plans again to refresh the fleet."""
        self.weights_version = weights_version
        self._params = _replica_params(params, self.replicas)
        self.plan_source = "live"
        for key in list(self._exec[0]):
            self._build_bucket(key)
        self.warm()

    # ---- queueing ---------------------------------------------------------
    def submit(self, x, *, image: Optional[int] = None) -> int:
        """Enqueue one request (a batch of ``x.shape[0]`` images).
        Raises ``RequestTooLarge`` when no bucket fits it."""
        if image is None and self.policy.image_sizes:
            image = int(x.shape[-1])
        try:
            self.policy.bucket_for(int(x.shape[0]), image)  # validate early
        except RequestTooLarge:
            self._n_rejected += 1
            raise
        now = self._clock()
        if self._t_first_submit is None:
            self._t_first_submit = now
        rid = next(self._rid)
        self._queue.append(_Request(rid=rid, x=x, t_arrival=now,
                                    image=image))
        self._queue_depth_max = max(self._queue_depth_max,
                                    len(self._queue))
        return rid

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def _form_batch(self, *, force: bool) -> Optional[list]:
        """FIFO-pack the queue head into one bucket batch.  The batch
        launches when it fills ``max_batch`` rows, when the oldest
        request has waited out the batching window, or on ``force``
        (end-of-trace flush).  Baseline modes never coalesce."""
        if not self._queue:
            return None
        head = self._queue[0]
        if self.mode != "bucketed":
            self._queue.popleft()
            return [head]
        take, rows = [], 0
        skipped = collections.deque()
        while self._queue:
            r = self._queue.popleft()
            if r.image != head.image:
                skipped.append(r)
                continue
            if rows + r.rows > self.policy.max_batch:
                skipped.append(r)
                break
            take.append(r)
            rows += r.rows
        while self._queue:
            skipped.append(self._queue.popleft())
        self._queue = skipped
        full = rows >= self.policy.max_batch
        waited = (self._clock() - head.t_arrival) >= self.window_s
        if full or waited or force:
            return take
        # window still open and the bucket is not full: requeue in order
        for r in reversed(take):
            self._queue.appendleft(r)
        return None

    # ---- execution --------------------------------------------------------
    def drain(self, *, force: bool = False) -> int:
        """Run formable batches until the queue empties or the batching
        window holds the remainder back; returns batches executed.
        Draining an empty queue is a no-op returning 0."""
        n = 0
        while True:
            reqs = self._form_batch(force=force)
            if reqs is None:
                return n
            self._run_batch(reqs)
            n += 1

    def _label(self, bucket: int, image) -> str:
        return f"b{bucket}" if image is None else f"b{bucket}i{image}"

    def _run_batch(self, reqs: list) -> None:
        import jax
        import jax.numpy as jnp
        rows = sum(r.rows for r in reqs)
        image = reqs[0].image
        if self.mode == "pad-max":
            bucket = self.policy.max_batch
        elif self.mode == "replan":
            bucket = rows                      # exact shape, no padding
        else:
            bucket = self.policy.bucket_for(rows, image)
        key = (bucket, image)
        replica = self._rr
        self._rr = (self._rr + 1) % self.replicas
        t0 = self._clock()
        ex = self._executor(key, replica)      # replan: builds here
        parts = [r.x for r in reqs]
        if rows < bucket:
            parts.append(jnp.zeros((bucket - rows,) + tuple(
                reqs[0].x.shape[1:]), reqs[0].x.dtype))
        xpad = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        y = ex(xpad)
        if self.timing == "per-batch":
            jax.block_until_ready(y)
        t1 = self._clock()
        self._replica_batches[replica] += 1
        self._t_last_done = t1
        st = self._stats.setdefault(self._label(bucket, image),
                                    _BucketStats())
        st.n_batches += 1
        st.real_rows += rows
        st.padded_rows += bucket
        st.service_s.append(t1 - t0)
        off = 0
        for r in reqs:
            st.n_requests += 1
            st.latencies_s.append(t1 - r.t_arrival)
            if self._collect:
                self.results[r.rid] = y[off:off + r.rows]
            off += r.rows
        if self.timing == "async":
            self._pending.append(y)

    def finish(self) -> None:
        """Block until every dispatched batch completed (async mode);
        closes the wall-clock window the throughput is computed over."""
        import jax
        if self._pending:
            jax.block_until_ready(self._pending)
            self._pending = []
            self._t_last_done = self._clock()

    # ---- accounting -------------------------------------------------------
    def report(self) -> dict:
        """Per-bucket latency percentiles + occupancy and engine-wide
        throughput/queue/cache stats (all derived from per-request
        accounting — nothing here times a bare dispatch unless
        ``timing="async"``, which the report flags)."""
        from repro.conv.plan import plan_cache_info
        buckets = {}
        all_lat: list = []
        total_req = total_real = total_padded = 0
        for label, st in self._stats.items():
            all_lat.extend(st.latencies_s)
            buckets[label] = {
                "p50_us": _percentile(st.latencies_s, 50) * 1e6,
                "p99_us": _percentile(st.latencies_s, 99) * 1e6,
                "service_p50_us": _percentile(st.service_s, 50) * 1e6,
                "n_requests": st.n_requests,
                "n_batches": st.n_batches,
                "occupancy": (st.real_rows / st.padded_rows
                              if st.padded_rows else float("nan")),
            }
            total_req += st.n_requests
            total_real += st.real_rows
            total_padded += st.padded_rows
        wall = None
        if self._t_first_submit is not None and \
                self._t_last_done is not None:
            wall = max(self._t_last_done - self._t_first_submit, 1e-9)
        misses_after_warm = None
        if self._warm_plan_misses is not None:
            misses_after_warm = (plan_cache_info().misses
                                 - self._warm_plan_misses)
        return {
            "mode": self.mode,
            "timing": self.timing,
            "replicas": self.replicas,
            "window_s": self.window_s,
            "buckets": buckets,
            "p50_us": _percentile(all_lat, 50) * 1e6,
            "p99_us": _percentile(all_lat, 99) * 1e6,
            "n_requests": total_req,
            "n_rejected": self._n_rejected,
            "real_rows": total_real,
            "padded_rows": total_padded,
            "occupancy": (total_real / total_padded if total_padded
                          else float("nan")),
            "wall_s": wall,
            "throughput_rows_s": (total_real / wall if wall else None),
            "queue_depth_max": self._queue_depth_max,
            "replica_batches": list(self._replica_batches),
            "plan_cache_misses_after_warmup": misses_after_warm,
            "startup_s": self.startup_s,
            "plan_source": self.plan_source,
        }

    def bucket_report(self) -> dict:
        """Cross-bucket plan-dedupe/cost summary
        (``BucketedNetworkPlan.report`` semantics over this engine's
        buckets, keyed by bucket label).  Unavailable on an engine
        started from a plan artifact (no live ``NetworkPlan`` objects)."""
        if not self.nets:
            raise RuntimeError(
                "bucket_report needs live-planned buckets (this engine "
                "loaded an AOT plan artifact)")
        from repro.conv.netplan import _bucket_report
        nets = {self._label(b, img): net
                for (b, img), net in self.nets.items()}
        return _bucket_report(nets)

    def bench_rows(self, prefix: str = "serve") -> dict:
        """The report in ``BENCH_conv.json`` schema: one row per bucket
        per metric (``serve/<bucket>/{p50,p99,occupancy}``), percentiles
        riding the entry's tolerated ``percentiles`` field so the
        baseline gate can hold serving SLOs."""
        rep = self.report()
        config = {"mode": rep["mode"], "replicas": rep["replicas"],
                  "window_s": rep["window_s"], "timing": rep["timing"]}
        rows = {}
        for label, b in rep["buckets"].items():
            pcts = {"p50": b["p50_us"], "p99": b["p99_us"]}
            meta = dict(config, n_requests=b["n_requests"],
                        n_batches=b["n_batches"])
            rows[f"{prefix}/{label}/p50"] = {
                "us_per_call": b["p50_us"], "percentiles": pcts,
                "config": meta}
            rows[f"{prefix}/{label}/p99"] = {
                "us_per_call": b["p99_us"], "percentiles": pcts,
                "config": meta}
            # occupancy is a 0..1 ratio riding the same schema (the
            # gate's min-us floor keeps it out of ratio comparisons)
            rows[f"{prefix}/{label}/occupancy"] = {
                "us_per_call": b["occupancy"], "config": meta}
        return rows


def _replica_params(params: dict, replicas: int) -> list:
    """One param pytree per replica.  With one replica the caller's
    arrays are used as-is, so repeat engine builds over the same params
    dedupe through the prepared cache (keyed ``(plan, id(kernel))``);
    multiple replicas get ``device_put`` copies round-robin over the
    visible devices — distinct arrays, so each replica owns its own
    prepared state (and its own device under an emulated mesh)."""
    if replicas == 1:
        return [dict(params)]
    import jax
    devices = jax.devices()
    return [jax.device_put(dict(params), devices[r % len(devices)])
            for r in range(replicas)]


def _chain_forward(prepared, x):
    """Default forward: the prepared layers chained in declaration
    order, no epilogue operands (nets whose plans fuse bias/residual
    pass a custom ``forward`` closing over those arrays)."""
    for name in prepared:
        x = prepared[name](x)
    return x


# --------------------------------------------------------------------------
# Trace replay
# --------------------------------------------------------------------------

def run_trace(engine: ServeEngine, trace: Sequence[TraceRequest], *,
              make_input: Callable, realtime: bool = True,
              sleep: Callable = time.sleep) -> dict:
    """Replay a trace through the engine; returns ``engine.report()``.

    ``realtime=True`` sleeps each request to its arrival offset and
    drains between arrivals — latencies reflect the trace's offered
    rate.  ``realtime=False`` is the deterministic burst replay: the
    whole trace is submitted up front and then drained, so every
    strategy faces the IDENTICAL backlog (the fair A/B for the
    pad-max/replan baselines — no sleeps, no rate tuning).
    ``make_input(batch, image) -> x``."""
    t0 = engine._clock()
    for tr in trace:
        if realtime:
            dt = tr.t - (engine._clock() - t0)
            if dt > 0:
                sleep(dt)
        engine.submit(make_input(tr.batch, tr.image), image=tr.image)
        if realtime:
            engine.drain()
    engine.drain(force=True)
    engine.finish()
    return engine.report()
