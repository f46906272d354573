"""Fault-tolerant batch-serving front end for the BoW/CV pipeline.

The paper's fused pipelines only matter in production if they *fail
safe*: a corrupt plan table, a lowering error or a NaN-poisoned frame
must degrade, not take down `pipeline.predict` with a raw traceback.
`CvEngine` hardens the path end to end:

  * **Batching + padding-to-bucket** — requests are grouped by the
    smallest bucket shape that fits (edge-padded), so a handful of
    canonical shapes cover all traffic and the measured-mode plan table
    (`autotune`) hits instead of re-keying per odd shape.
  * **Degradation ladder** — every batch executes under
    ``streaming -> tiled2d -> window -> chain_ref``: a rung that raises (lowering
    error, injected fault, plan-cache damage) is retried with backoff,
    then the engine degrades to the next rung and records a structured
    `core.faultinject` degradation event.  The `chain_ref` floor is pure
    staged jnp — always lowerable, always correct.  The engine passes the
    rung as an explicit `mode=` argument down the pipeline (NOT via the
    process default: jit traces bake the plan in at trace time, so a
    global flip would be invisible to already-traced shapes).
  * **Admission control** — NaN/Inf float frames are sanitized (or
    rejected, ``bad_input="reject"``) with an event; malformed frames
    (bad rank/dtype) get a per-request error Response instead of
    poisoning the batch.
  * **Deadlines + bounded retry** — per-request deadlines are checked
    before dispatch (expired requests are answered without compute) and
    after; rung retries are bounded with exponential backoff.
  * **Warm plan table** — ``warm()`` runs `autotune.measure_chain` per
    bucket under a deadline and a `train.fault.StragglerWatchdog`;
    a measurement timeout records an event and the engine serves via
    the halo heuristic instead.
  * **Sharded fan-out** — given a multi-device CV mesh
    (``CvEngine(mesh=make_cv_mesh())``), batches route through
    `serve.shard_dispatch.ShardDispatcher`: the batch fans out over the
    "data" axis, each shard is an independent fault domain with its own
    ladder walk, failed/lost shards re-dispatch to devices the
    device-health ledger still trusts, and a circuit breaker
    short-circuits known-bad (signature, bucket, rung) combinations.
    Responses carry the serving shard/device; single-device hosts (or
    ``mesh=None``) serve exactly as before.

Faults are injected (deterministically) via ``REPRO_FAULT_SPEC`` /
`core.faultinject` — the chaos CI cell runs this engine's smoke workload
(`python -m repro.serve.cv_engine --smoke`) under every fault class and
requires zero unhandled exceptions.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import faultinject
from repro.core import autotune
from repro.cv import classify, features, pipeline
from repro.cv.config import PipelineConfig, resolve_config, _UNSET
from repro.kernels import stencil
from repro.serve.shard_dispatch import ShardDispatcher
from repro.train.fault import StragglerWatchdog

DEFAULT_BUCKETS = ((32, 32), (64, 64), (128, 128), (256, 256))
DEFAULT_LADDER = stencil.DEGRADATION_LADDER   # streaming -> tiled2d -> window -> ref


@dataclass
class Request:
    """One frame in; deadline is absolute (time.monotonic() seconds)."""
    image: object
    deadline: float | None = None


@dataclass
class Response:
    index: int                       # position in the submitted workload
    ok: bool
    desc: np.ndarray | None = None   # extract task: (max_kp, 128) descriptors
    valid: np.ndarray | None = None
    pred: int | None = None          # classify task
    image: np.ndarray | None = None  # filter task: the frame's chain output
    bucket: tuple | None = None
    plan: str | None = None          # the rung that produced the answer
    retries: int = 0
    degraded: bool = False
    deadline_missed: bool = False
    shard: int | None = None         # data-axis shard that served this request
    device: str | None = None        # device_key of the serving device
    error: str | None = None
    events: list = field(default_factory=list)
    latency_s: float = 0.0


class CvEngine:
    """Batch-serving engine over `cv.pipeline` with a degradation ladder.

    task="extract" serves descriptor sets (no model needed);
    task="classify" serves class predictions through the
    `cv.classify.ClassifyPlan` tail (pass a trained `BowSvmModel` /
    `BowGbdtModel`); task="filter" serves each frame's output of one
    `kernels.stencil` chain (pass its stages as ``chain=``), in the
    frame's own dtype and shape.  Pipeline knobs come in via ``config=``
    (`cv.config.PipelineConfig`); the old `n_octaves=`/`preprocess=`
    kwargs survive as deprecation shims."""

    def __init__(self, model=None, config: PipelineConfig | None = None, *,
                 chain=None, buckets=DEFAULT_BUCKETS,
                 max_batch: int = 64, ladder=DEFAULT_LADDER,
                 max_retries: int = 1, backoff_s: float = 0.01,
                 bad_input: str = "sanitize", max_kp=_UNSET,
                 n_octaves=_UNSET, preprocess=_UNSET,
                 capture_frames: bool = False, watchdog=None,
                 mesh=None, dispatcher: ShardDispatcher | None = None):
        if bad_input not in ("sanitize", "reject"):
            raise ValueError(f"bad_input must be 'sanitize' or 'reject', "
                             f"got {bad_input!r}")
        ladder = tuple(ladder)
        if not ladder:
            raise ValueError("ladder must have at least one rung")
        for rung in ladder:
            if rung not in stencil.MODES:
                raise ValueError(f"unknown ladder rung {rung!r}")
        if model is not None and chain is not None:
            raise ValueError("pass model= (classify) OR chain= (filter), "
                             "not both")
        if chain is not None:
            chain = tuple(chain)
            if not chain or stencil.resolve_chain(chain)[-1][6] != 1:
                raise ValueError("chain= must be stages that end in one "
                                 "output band")
        cfg = resolve_config(config, where="CvEngine", max_kp=max_kp,
                             n_octaves=n_octaves, preprocess=preprocess)
        self.model = model
        self.chain = chain
        self.config = cfg
        self.plan = (classify.build_plan(model, cfg)
                     if model is not None else None)
        # the batch program's traced argument: the classifier tail, or the
        # chain's weights, so that neither is compiled in as a constant
        self._program_arg = (stencil.ir.flat_weights(chain)
                             if chain is not None else self.plan)
        self.buckets = tuple(sorted(tuple(b) for b in buckets))
        self.max_batch = int(max_batch)
        self.ladder = ladder
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.bad_input = bad_input
        self.max_kp = int(cfg.max_kp)
        self.n_octaves = int(cfg.n_octaves)
        self.preprocess = bool(cfg.preprocess)
        self.capture_frames = bool(capture_frames)
        self.watchdog = watchdog if watchdog is not None else \
            StragglerWatchdog(threshold=4.0, warmup=2)
        if dispatcher is not None and mesh is not None:
            raise ValueError("pass mesh= OR dispatcher=, not both")
        if dispatcher is None and mesh is not None:
            dispatcher = ShardDispatcher(mesh, ladder=ladder)
        self.dispatcher = dispatcher
        self.captured: list = []     # (bucket, canonical batch) when capturing
        self._submits = 0            # index of the last submit, for its span
        self._local_fns: dict = {}   # rung -> jit(_batch_body at that rung)
        self.stats = {"served": 0, "errors": 0, "degraded_batches": 0,
                      "retries": 0, "deadline_missed": 0, "sanitized": 0,
                      "sharded_batches": 0, "shard_failures": 0,
                      "batch_traces": 0}

    @property
    def task(self) -> str:
        if self.chain is not None:
            return "filter"
        return "classify" if self.model is not None else "extract"

    @property
    def signature(self) -> str:
        """Workload identity half of the circuit-breaker key: one string
        per (task, pipeline knobs or chain) — bucket and rung complete the
        key."""
        if self.chain is not None:
            return f"cv:filter:{stencil.ir.spec_of(self.chain)}"
        return (f"cv:{self.task}:kp{self.max_kp}:oct{self.n_octaves}"
                f":pre{int(self.preprocess)}")

    # -- admission -----------------------------------------------------------

    def _admit(self, req: Request, idx: int):
        """One frame -> (canonical np array, events) or an error Response."""
        events = []
        img = req.image
        arr = np.asarray(img)
        if arr.ndim not in (2, 3) or (arr.ndim == 3 and arr.shape[-1] not in (1, 3)):
            return None, Response(
                index=idx, ok=False,
                error=f"bad_rank: expected (H, W) or (H, W, {{1,3}}), "
                      f"got {arr.shape}")
        if not (np.issubdtype(arr.dtype, np.floating)
                or arr.dtype == np.uint8):
            return None, Response(
                index=idx, ok=False,
                error=f"bad_dtype: expected uint8/float, got {arr.dtype}")
        arr, fired = faultinject.poison(arr, site=f"admit:{idx}")
        if np.issubdtype(arr.dtype, np.floating):
            bad = ~np.isfinite(arr)
            if bad.any():
                if self.bad_input == "reject":
                    return None, Response(
                        index=idx, ok=False,
                        error=f"bad_values: {int(bad.sum())} NaN/Inf pixels"
                              + (" (injected)" if fired else ""))
                arr = np.nan_to_num(arr, nan=0.0, posinf=255.0, neginf=0.0)
                events.append(faultinject.record_degradation(
                    stage="serve", from_plan="raw-input", to_plan="sanitized",
                    reason=f"{int(bad.sum())} NaN/Inf pixels zeroed/clamped",
                    detail=f"request {idx}", injected=fired))
                self.stats["sanitized"] += 1
        return arr, events

    # -- bucketing -----------------------------------------------------------

    def _bucket_of(self, shape) -> tuple | None:
        """Smallest bucket that fits (H, W); None = serve at exact shape."""
        h, w = shape[:2]
        if faultinject.should_fire("bucket_miss", site=f"bucket:{h}x{w}"):
            faultinject.record_degradation(
                stage="serve", from_plan="bucketed", to_plan="exact-shape",
                reason="bucket miss (injected): padding skipped",
                detail=f"{h}x{w}", injected=True)
            return None
        for bh, bw in self.buckets:
            if h <= bh and w <= bw:
                return (bh, bw)
        faultinject.record_degradation(
            stage="serve", from_plan="bucketed", to_plan="exact-shape",
            reason="frame larger than every bucket", detail=f"{h}x{w}")
        return None

    @staticmethod
    def _pad_to(arr: np.ndarray, bucket: tuple | None) -> np.ndarray:
        if bucket is None:
            return arr
        ph, pw = bucket[0] - arr.shape[0], bucket[1] - arr.shape[1]
        if ph == 0 and pw == 0:
            return arr
        pad = [(0, ph), (0, pw)] + [(0, 0)] * (arr.ndim - 2)
        return np.pad(arr, pad, mode="edge")

    # -- ladder execution ----------------------------------------------------

    def _batch_body(self, x, rung: str, arg=None):
        """Traceable per-rung batch computation: (B, H, W[, C]) jax array
        -> dict of batch-leading jax arrays.  No host sync, no timing —
        it must trace under `shard_map`, so both the local ladder
        (`_batch_fn`) and the sharded dispatcher run through it; the
        classify composition matches `pipeline.predict` numerically.
        `arg` stands in for the engine's own program argument (the
        classifier tail, or the chain's weights) where the caller passes
        it as a traced argument.

        The filter task runs its chain at the rung as one `fused_chain`
        launch.  The stencil rung maps onto the classifier tail's two
        rungs: the jnp floor ("ref") classifies through the staged oracle,
        every fused stencil rung classifies through the fused tail."""
        arg = self._program_arg if arg is None else arg
        if self.chain is not None:
            stages = stencil.ir.respec(stencil.ir.spec_of(self.chain), arg)
            gray = x.ndim == 3           # (B, H, W): one channel, no axis
            out = stencil.fused_chain(x[..., None] if gray else x, stages,
                                      mode=rung)
            return {"image": out[..., 0] if gray else out}
        plan = arg
        feats = pipeline.extract_features(
            x, self.config.replace(mode=rung), validate=False)
        if plan is not None:
            cmode = "ref" if rung == "ref" else "fused"
            hists = plan.histograms(feats["desc"], feats["valid"],
                                    mode=cmode)
            return {"pred": plan.classify(hists, mode=cmode)}
        return {"desc": feats["desc"], "valid": feats["valid"]}

    def _batch_fn(self, x, rung: str):
        """One batch of the local ladder through the rung's compiled
        program: `jax.jit` of `_batch_body` at that rung, built on first
        use, which keeps one executable per (batch shape, dtype).  The
        classifier tail or the chain's weights go in as an argument, so
        their arrays are not compiled in.  Lowering errors fire while a
        shape's first call traces, and a trace that raised caches nothing,
        so the next call of that shape traces again.  ``stats["batch_traces"]`` counts
        the traces, not the calls."""
        fn = self._local_fns.get(rung)
        if fn is None:
            def traced(xs, arg):
                self.stats["batch_traces"] += 1
                return self._batch_body(xs, rung, arg)
            fn = self._local_fns[rung] = jax.jit(traced)
        return fn(x, self._program_arg)

    def _image_of(self, result: dict, row: int, hw) -> np.ndarray | None:
        """A filter answer cut from its bucket back to the frame's own
        output size: the edge padding reads as the chain's replicate
        border, so the rest of the answer is what the frame alone gives."""
        if "image" not in result:
            return None
        h, w = hw
        for op, mode, *_ in stencil.resolve_chain(self.chain):
            if mode == "map":
                h, w = stencil.stage_out_hw(op, h, w)
        return result["image"][row][:h, :w]

    def _run_batch(self, batch: np.ndarray, rung: str):
        """One canonical batch through the pipeline at one explicit rung."""
        with TraceAnnotation("serve.h2d"):
            x = jnp.asarray(batch)
        with TraceAnnotation("serve.dispatch"):
            out = self._batch_fn(x, rung)
        with TraceAnnotation("serve.wait"):
            out = jax.block_until_ready(out)
        with TraceAnnotation("serve.d2h"):
            return {k: np.asarray(v) for k, v in out.items()}

    def _run_ladder(self, batch: np.ndarray, deadlines=()):
        """Ladder + bounded retry; returns (result, plan, retries, events)
        or raises only if the FINAL rung fails every attempt.

        `deadlines` carries the batch's absolute request deadlines: a
        retry whose backoff sleep would overrun the tightest one is
        abandoned (deadline_missed, NOT a retry) and the ladder degrades
        immediately — sleeping through a deadline to honor the retry
        budget would answer every request in the batch late."""
        events, retries = [], 0
        nearest = min((d for d in deadlines if d is not None), default=None)
        for i, rung in enumerate(self.ladder):
            last_rung = i == len(self.ladder) - 1
            for attempt in range(self.max_retries + 1):
                try:
                    return self._run_batch(batch, rung), rung, retries, events
                except (ValueError, NotImplementedError):
                    raise            # misconfiguration: no rung may mask it
                except Exception as e:
                    injected = isinstance(e, faultinject.InjectedFault)
                    if attempt < self.max_retries:
                        sleep_s = self.backoff_s * (2 ** attempt)
                        if (nearest is not None
                                and time.monotonic() + sleep_s > nearest):
                            self.stats["deadline_missed"] += 1
                            events.append(faultinject.record_degradation(
                                stage="serve", from_plan=rung,
                                to_plan=rung if last_rung
                                else self.ladder[i + 1],
                                reason=f"retry abandoned: {sleep_s:.3f}s "
                                       f"backoff would sleep past the batch "
                                       f"deadline ({type(e).__name__}: {e})",
                                injected=injected))
                            if last_rung:
                                raise
                            break    # degrade now instead of sleeping late
                        retries += 1
                        self.stats["retries"] += 1
                        events.append(faultinject.record_degradation(
                            stage="serve", from_plan=rung, to_plan=rung,
                            reason=f"retry {attempt + 1}/{self.max_retries}: "
                                   f"{type(e).__name__}: {e}",
                            injected=injected))
                        time.sleep(sleep_s)
                        continue
                    if last_rung:
                        raise
                    events.append(faultinject.record_degradation(
                        stage="serve", from_plan=rung,
                        to_plan=self.ladder[i + 1],
                        reason=f"rung failed after {attempt + 1} attempt(s): "
                               f"{type(e).__name__}: {e}",
                        injected=injected))
        raise RuntimeError("unreachable: ladder loop exhausted")

    # -- public API ----------------------------------------------------------

    def warm(self, bucket: tuple, *, channels: int = 3, n: int = 1,
             deadline_s: float | None = 5.0, seed: int = 0) -> dict | None:
        """Warm the plan table for one bucket's octave chain; a measurement
        timeout degrades to heuristic routing instead of raising."""
        # programs traced at an explicit rung never read the plan table,
        # so the engine's compiled programs outlive a re-measurement
        h, w = bucket
        gen = np.random.default_rng(seed)
        img = jnp.asarray(gen.random((h, w), dtype=np.float32))
        chain = features.octave_chain(with_next_base=False)
        # route the warm measurement through the health ledger: it runs on
        # the best healthy device and its outcome counts like a shard's
        dev = None
        if self.dispatcher is not None:
            dev = self.dispatcher.health.pick()
            if dev is not None and hasattr(dev, "platform"):
                img = jax.device_put(img, dev)
        t0 = time.monotonic()
        try:
            table = autotune.measure_chain(img, chain, n=n,
                                           deadline_s=deadline_s,
                                           watchdog=self.watchdog)
            if dev is not None:
                self.dispatcher.health.record_success(
                    dev, time.monotonic() - t0)
            return table
        except autotune.MeasureTimeout as e:
            faultinject.record_degradation(
                stage="serve", from_plan="measured-plan",
                to_plan="heuristic",
                reason=f"warm({h}x{w}) timed out: {e}",
                injected=isinstance(e.__cause__, faultinject.InjectedFault)
                or "injected" in str(e))
            if dev is not None:
                self.dispatcher.health.record_failure(
                    dev, reason=f"warm({h}x{w}) timeout: {e}")
            return None

    def submit(self, workload) -> list[Response]:
        """Serve a workload (arrays or `Request`s) -> one Response each.

        Each phase runs under a `jax.profiler.TraceAnnotation`, so a
        profiler capture of a serving process shows them beside the
        device ops: ``serve.submit`` (the whole call; keywords ``submit``,
        its index, and ``task``: extract, classify or filter) holds
        ``serve.admit`` (admission, bucketing, padding), then per group
        batch ``serve.stack``, ``serve.h2d`` (copy to the device),
        ``serve.dispatch`` (the rung's compiled program: traced and
        compiled on a shape's first call, else looked up; enqueue),
        ``serve.wait`` (the device computing), ``serve.d2h`` (the fetch)
        and ``serve.assemble`` (the Responses); the sharded path puts
        ``serve.fanout`` around its transfers and dispatch."""
        self._submits += 1
        with TraceAnnotation("serve.submit", submit=self._submits,
                             task=self.task):
            reqs = [r if isinstance(r, Request) else Request(r) for r in workload]
            responses: list[Response | None] = [None] * len(reqs)
            groups: dict = {}
            with TraceAnnotation("serve.admit"):
                for idx, req in enumerate(reqs):
                    if req.deadline is not None and time.monotonic() > req.deadline:
                        self.stats["deadline_missed"] += 1
                        responses[idx] = Response(index=idx, ok=False,
                                                  deadline_missed=True,
                                                  error="deadline_exceeded")
                        continue
                    arr, admitted = self._admit(req, idx)
                    if arr is None:
                        responses[idx] = admitted       # error Response
                        continue
                    bucket = self._bucket_of(arr.shape)
                    canon = self._pad_to(arr, bucket)
                    gkey = (bucket or canon.shape[:2], canon.shape, str(canon.dtype))
                    groups.setdefault(gkey, []).append(
                        (idx, canon, admitted, arr.shape[:2]))

            # batched execution: sharded fan-out when a multi-device dispatcher
            # is attached, local ladder otherwise
            sharded = self.dispatcher is not None and self.dispatcher.n_shards > 1
            for (bucket, _, _), members in groups.items():
                for lo in range(0, len(members), self.max_batch):
                    part = members[lo:lo + self.max_batch]
                    idxs = [m[0] for m in part]
                    with TraceAnnotation("serve.stack"):
                        batch = np.stack([m[1] for m in part])
                    if self.capture_frames:
                        self.captured.append((tuple(bucket), batch))
                    t0 = time.monotonic()
                    if sharded:
                        self._submit_sharded(part, idxs, batch, bucket, reqs, responses, t0)
                    else:
                        self._submit_local(part, idxs, batch, bucket, reqs, responses, t0)
            return responses  # responses[i] is never None past this point

    def _submit_local(self, part, idxs, batch, bucket, reqs,
                      responses, t0) -> None:
        """One group batch through the local ladder."""
        try:
            result, plan, retries, events = self._run_ladder(
                batch, [reqs[idx].deadline for idx in idxs])
        except ValueError:
            raise            # caller bug, not a serving fault
        except Exception as e:
            for idx in idxs:
                responses[idx] = Response(
                    index=idx, ok=False, bucket=tuple(bucket),
                    error=f"floor_rung_failed: {type(e).__name__}: {e}",
                    events=[ev for m in part for ev in m[2]])
                self.stats["errors"] += 1
            return
        dt = time.monotonic() - t0
        degraded = plan != self.ladder[0] or bool(events)
        if degraded:
            self.stats["degraded_batches"] += 1
        with TraceAnnotation("serve.assemble"):
            for k, idx in enumerate(idxs):
                admit_events = part[k][2]
                missed = self._deadline_missed(reqs[idx], idx)
                responses[idx] = Response(
                    index=idx, ok=True,
                    desc=result["desc"][k] if "desc" in result else None,
                    valid=result["valid"][k] if "valid" in result else None,
                    pred=(int(result["pred"][k])
                          if "pred" in result else None),
                    image=self._image_of(result, k, part[k][3]),
                    bucket=tuple(bucket), plan=plan, retries=retries,
                    degraded=degraded, deadline_missed=missed,
                    events=list(admit_events) + list(events),
                    latency_s=dt)
                self.stats["served"] += 1

    def _deadline_missed(self, req: Request, idx: int) -> bool:
        missed = (req.deadline is not None
                  and time.monotonic() > req.deadline)
        if missed:
            self.stats["deadline_missed"] += 1
            faultinject.record_degradation(
                stage="serve", from_plan="on-time", to_plan="late",
                reason="deadline missed post-compute",
                detail=f"request {idx}")
        return missed

    def _submit_sharded(self, part, idxs, batch, bucket, reqs,
                        responses, t0) -> None:
        """One group batch through the sharded dispatcher: per-shard fault
        domains, per-request Responses carrying shard/device identity."""
        try:
            report = self.dispatcher.dispatch(
                batch, self._batch_body, signature=self.signature,
                bucket=tuple(bucket), mode=self.ladder[0])
        except ValueError:
            raise                    # caller bug, not a serving fault
        except Exception as e:       # dispatcher invariant broke: fail batch
            for k, idx in enumerate(idxs):
                responses[idx] = Response(
                    index=idx, ok=False, bucket=tuple(bucket),
                    error=f"dispatch_failed: {type(e).__name__}: {e}",
                    events=list(part[k][2]))
                self.stats["errors"] += 1
            return
        dt = time.monotonic() - t0
        self.stats["sharded_batches"] += 1
        degraded_batch = False
        with TraceAnnotation("serve.assemble"):
            for k, idx in enumerate(idxs):
                admit_events = list(part[k][2])
                sres, row = report.result_of(k)
                events = admit_events + list(report.events) + list(sres.events)
                if not sres.ok:
                    self.stats["errors"] += 1
                    self.stats["shard_failures"] += 1
                    responses[idx] = Response(
                        index=idx, ok=False, bucket=tuple(bucket),
                        shard=sres.shard, device=sres.device,
                        error=f"shard_failed: {sres.error}", events=events)
                    continue
                degraded = (sres.plan != self.ladder[0] or sres.redispatches > 0
                            or bool(events))
                degraded_batch = degraded_batch or degraded
                missed = self._deadline_missed(reqs[idx], idx)
                responses[idx] = Response(
                    index=idx, ok=True,
                    desc=(sres.value["desc"][row]
                          if "desc" in sres.value else None),
                    valid=(sres.value["valid"][row]
                           if "valid" in sres.value else None),
                    pred=(int(sres.value["pred"][row])
                          if "pred" in sres.value else None),
                    image=self._image_of(sres.value, row, part[k][3]),
                    bucket=tuple(bucket), plan=sres.plan,
                    retries=sres.redispatches, degraded=degraded,
                    deadline_missed=missed, shard=sres.shard,
                    device=sres.device, events=events, latency_s=dt)
                self.stats["served"] += 1
        if degraded_batch:
            self.stats["degraded_batches"] += 1

    def extract(self, imgs) -> list[Response]:
        return self.submit(imgs)

    def classify(self, imgs) -> list[Response]:
        if self.model is None:
            raise ValueError("classify needs a trained model "
                             "(BowSvmModel or BowGbdtModel)")
        return self.submit(imgs)

    def filter(self, imgs) -> list[Response]:
        if self.chain is None:
            raise ValueError("filter needs a stencil chain (chain=)")
        return self.submit(imgs)


# ---------------------------------------------------------------------------
# LM serving steps (folded from the old serve/engine.py so there is ONE
# serving front end): prefill + greedy decode against sharded KV/state
# caches.  serve_step (the dry-run target for decode_* / long_* shapes)
# consumes and produces the cache (donated); the KV time axis is sharded
# over "model" (split-K decode — the partial-softmax collectives are
# inserted by SPMD).  The LM model/sharding imports stay lazy: the CV
# batch engine above must import (and chaos-test) without them.
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, mesh):
    from repro.models import lm
    from repro.sharding import rules

    hint = rules.make_hint(mesh, cfg)

    def prefill_step(params, batch):
        logits, cache = lm.prefill(params, cfg, batch, hint=hint)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def make_decode_step(cfg, mesh, *, greedy: bool = True):
    from repro.models import lm
    from repro.sharding import rules

    hint = rules.make_hint(mesh, cfg)

    def serve_step(params, cache, tokens):
        """tokens (B, 1) int32 -> (next_token (B,), new cache)."""
        logits, new_cache = lm.decode_step(params, cfg, tokens, cache, hint=hint)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return serve_step


def generate(params, cfg, prompt_tokens, *, steps: int, mesh, cache_len: int | None = None,
             extras: dict | None = None):
    """Simple greedy generation loop (prefill + repeated decode) for the
    examples; runs on whatever mesh is active."""
    from repro.models import lm

    B, S = prompt_tokens.shape
    cache_len = cache_len or (S + steps)
    batch = {"tokens": prompt_tokens, **(extras or {})}
    prefill_step = make_prefill_step(cfg, mesh)
    decode = jax.jit(make_decode_step(cfg, mesh), donate_argnums=(1,))
    tok, pcache = jax.jit(prefill_step)(params, batch)
    # re-home the prefill cache into fixed-size decode buffers
    cache = lm.init_cache(cfg, B, cache_len,
                          ctx_len=pcache.get("ctx", jnp.zeros((B, 0, 1))).shape[1] if "ctx" in pcache else None)
    cache = _adopt_prefill(cache, pcache, cfg)
    out = [tok]
    for _ in range(steps - 1):
        tok, cache = decode(params, cache, out[-1][:, None])
        out.append(tok)
    return jnp.stack(out, axis=1)


def _adopt_prefill(cache, pcache, cfg):
    """Copy prefill KV (length S) into decode buffers (length cache_len)."""
    cache = dict(cache)
    cache["pos"] = pcache["pos"]
    new_groups = []
    for (kind, _), buf, pre in zip(cfg.blocks, cache["groups"], pcache["groups"]):
        if kind in ("attn", "moe", "enc", "dec", "mla", "mla_moe"):
            def put(b, p):
                if b.ndim >= 3 and p.ndim == b.ndim and p.shape[2] <= b.shape[2]:
                    return jax.lax.dynamic_update_slice(b, p.astype(b.dtype), (0,) * b.ndim)
                return p.astype(b.dtype) if p.shape == b.shape else b
            merged = jax.tree.map(put, buf, pre)
        else:
            merged = jax.tree.map(lambda b, p: p.astype(b.dtype) if p.shape == b.shape else b, buf, pre)
        new_groups.append(merged)
    cache["groups"] = new_groups
    if "ctx" in pcache:
        cache["ctx"] = pcache["ctx"]
    if cfg.shared_attn_every:
        merged_shared = []
        for buf, pre in zip(cache["shared"], pcache["shared"]):
            def put(b, p):
                if p.ndim == b.ndim and p.shape[1] <= b.shape[1]:
                    return jax.lax.dynamic_update_slice(b, p.astype(b.dtype), (0,) * b.ndim)
                return b
            merged_shared.append(jax.tree.map(put, buf, pre))
        cache["shared"] = merged_shared
    return cache


# ---------------------------------------------------------------------------
# smoke workload: `make serve-smoke` / the chaos CI cell
# ---------------------------------------------------------------------------

def _smoke(verbose: bool = True) -> int:
    """Mixed-shape workload through the engine under whatever
    REPRO_FAULT_SPEC is active; exit nonzero on any unexpected failure."""
    gen = np.random.default_rng(7)
    work = []
    for i in range(16):
        h, w = int(gen.integers(24, 40)), int(gen.integers(24, 40))
        if i % 3 == 0:
            work.append(gen.random((h, w), dtype=np.float32))
        else:
            work.append(gen.integers(0, 256, (h, w, 3), dtype=np.uint8))
    work.append(np.zeros((8, 8, 2), dtype=np.uint8))        # bad rank -> error
    mesh = None
    if len(jax.devices()) > 1:       # multi-device host: shard the fan-out
        from repro.launch.mesh import make_cv_mesh
        mesh = make_cv_mesh()
    eng = CvEngine(buckets=((32, 32), (48, 48)), max_batch=8, max_kp=16,
                   mesh=mesh)
    faultinject.clear_degradation_log()
    res = eng.extract(work)
    n_ok = sum(r.ok for r in res)
    n_err = sum(not r.ok for r in res)
    n_deg = sum(r.degraded for r in res)
    assert all(r is not None for r in res), "unanswered request"
    assert n_ok == len(work) - 1, \
        f"expected every well-formed request served, got {n_ok}/{len(work) - 1}"
    assert not res[-1].ok and "bad_rank" in res[-1].error
    if verbose:
        spec = faultinject.registry()
        print(f"serve-smoke: {n_ok} ok / {n_err} rejected / {n_deg} degraded; "
              f"{len(faultinject.degradation_log())} degradation events; "
              f"faults={'on (' + ','.join(spec.specs) + ')' if spec else 'off'}")
        print(f"stats: {eng.stats}")
        if eng.dispatcher is not None:
            d = eng.dispatcher
            print(f"shards: {d.stats}; lost={d.lost_devices()}; "
                  f"quarantined={d.health.quarantined()}")
    return 0


if __name__ == "__main__":          # python -m repro.serve.cv_engine --smoke
    import argparse
    ap = argparse.ArgumentParser(description="CV serving engine tools")
    ap.add_argument("--smoke", action="store_true",
                    help="run the mixed-shape smoke workload (honors "
                         "REPRO_FAULT_SPEC) and exit nonzero on failure")
    a = ap.parse_args()
    if a.smoke:
        from repro.core import jaxcache
        jaxcache.enable()
        raise SystemExit(_smoke())
