"""ClassifyPlan — the classifier tail (quantize -> histogram -> classify)
behind one plan seam.

The redesign mirrors the stencil package's Plan -> executor split: a
frozen `ClassifyPlan` binds the trained model artifacts (codebook +
head parameters) to an execution mode and degradation ladder, and its
methods are the only way `cv.pipeline.predict` / `serve.cv_engine`
reach the classifier kernels.  Two rungs:

  fused  — `kernels.bow.bow_quantize_hist` (one launch per batch:
           descriptor blocks stream against the VMEM-resident codebook,
           running argmin + in-kernel segment-sum) then
           `kernels.bow.linear_score` (SVM head) or
           `kernels.gbdt.gbdt_score` (oblivious-tree GBDT head) — the
           whole tail in two launches.
  ref    — the staged jnp oracle (`kernels.ref.bow_hist_ref` /
           `svm_decision_ref` / `gbdt_scores_ref`), no Pallas launch.

Oracle contract: fused histograms are bit-identical to the staged ref
(shared  s = -2 d.c + |c|^2  arithmetic, order-independent {0,1}
weight sums); SVM scores are bit-identical (same contraction dims);
GBDT *leaf indices* are bit-identical while scores may differ by float
association (ulp-level) — `tests/test_classify_plan.py` pins all three.

Ladder semantics follow `kernels.stencil.ladder.run_ladder`: ValueError
(misconfiguration) always raises, any other fused-rung failure degrades
to ref with a recorded `core.faultinject` event, the final rung raises.
Mode resolution: explicit arg -> plan.mode -> measured autotune cache
(`core.autotune.cached_classify_mode`) -> "fused".
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import faultinject
from repro.core.vector import VectorConfig, DEFAULT
from repro.kernels import bow as kbow
from repro.kernels import gbdt as kgbdt
from repro.kernels import ref as kref
from repro.kernels.stencil.ladder import run_ladder

from .config import PipelineConfig
from .gbdt import GbdtModel

Array = jax.Array

# classifier-tail execution plans, fastest-first; ref is the staged jnp
# oracle floor (no Pallas launch, always lowerable)
CLASSIFY_MODES = ("fused", "ref")
CLASSIFY_LADDER = ("fused", "ref")


def resolve_classify_rungs(mode: str, ladder) -> tuple[str, ...]:
    """The rung sequence one classify call runs (the classifier-tail
    analogue of `stencil.ladder.resolve_rungs`): resolved plan first,
    then the ladder rungs after it, deduplicated; no ladder means the
    single-plan raise-on-failure contract."""
    if mode not in CLASSIFY_MODES:
        raise ValueError(f"ClassifyPlan: unknown mode {mode!r} "
                         f"(expected one of {CLASSIFY_MODES})")
    if not ladder:
        return (mode,)
    ladder = tuple(ladder)
    for m in ladder:
        if m not in CLASSIFY_MODES:
            raise ValueError(f"ClassifyPlan: unknown ladder rung {m!r}")
    tail = ladder[ladder.index(mode) + 1:] if mode in ladder else ladder
    rungs, seen = [mode], {mode}
    for m in tail:
        if m not in seen:
            rungs.append(m)
            seen.add(m)
    return tuple(rungs)


@dataclass(frozen=True, eq=False)
class ClassifyPlan:
    """Bound classifier tail: codebook + head parameters + execution plan.

    head: "svm" (w (C, K), b (C,)) or "gbdt" (`cv.gbdt.GbdtModel`).
    mode: None = autotune-cache-then-fused; "fused" | "ref" pins a rung.
    ladder: degradation ladder over CLASSIFY_MODES (None/() disables).
    """
    centroids: Array
    n_classes: int
    head: str = "svm"
    w: Array | None = None
    b: Array | None = None
    gbdt: GbdtModel | None = None
    vc: VectorConfig = DEFAULT
    mode: str | None = None
    ladder: tuple[str, ...] | None = CLASSIFY_LADDER
    normalize: bool = True

    def __post_init__(self):
        if self.ladder is not None and not isinstance(self.ladder, tuple):
            object.__setattr__(self, "ladder", tuple(self.ladder))
        if self.head == "svm":
            if self.w is None or self.b is None:
                raise ValueError("ClassifyPlan: head='svm' needs w and b")
        elif self.head == "gbdt":
            if self.gbdt is None:
                raise ValueError("ClassifyPlan: head='gbdt' needs a GbdtModel")
        else:
            raise ValueError(f"ClassifyPlan: unknown head {self.head!r}")

    @property
    def signature(self) -> str:
        """Stable autotune identity of this tail (head + problem shape)."""
        K, D = self.centroids.shape
        return f"classify:{self.head}:k{K}d{D}c{self.n_classes}"

    # -- mode resolution ----------------------------------------------------

    def resolve_mode(self, descs_shape, dtype, mode: str | None = None) -> str:
        """Explicit arg -> plan.mode -> measured cache -> "fused"."""
        if mode is not None:
            return mode
        if self.mode is not None:
            return self.mode
        from repro.core import autotune
        cached = autotune.cached_classify_mode(self, descs_shape, dtype)
        return cached if cached is not None else "fused"

    def _run(self, rung_fns: dict, mode: str | None, shape, dtype,
             stage: str):
        resolved = self.resolve_mode(shape, dtype, mode)
        rungs = resolve_classify_rungs(resolved, self.ladder)
        detail = f"{self.signature}|{'x'.join(map(str, shape))}|{dtype}"
        return run_ladder(rungs, lambda r: rung_fns[r](),
                          stage=stage, detail=detail)

    # -- stages -------------------------------------------------------------

    def histograms(self, descs: Array, valids: Array, *,
                   mode: str | None = None) -> Array:
        """descs (B, N, D) + valids (B, N) -> word histograms (B, K)."""
        def fused():
            faultinject.maybe_raise("lowering_error", site="classify:fused")
            return kbow.bow_quantize_hist(descs, valids, self.centroids,
                                          vc=self.vc,
                                          normalize=self.normalize)

        def ref():
            return kref.bow_hist_ref(descs, valids, self.centroids,
                                     normalize=self.normalize)

        return self._run({"fused": fused, "ref": ref}, mode, descs.shape,
                         jnp.dtype(descs.dtype).name, "classify_hist")

    def scores(self, hists: Array, *, mode: str | None = None) -> Array:
        """Histograms (B, K) -> decision scores (B, n_classes)."""
        def fused():
            faultinject.maybe_raise("lowering_error", site="classify:fused")
            if self.head == "svm":
                return kbow.linear_score(hists, self.w, self.b, vc=self.vc)
            m = self.gbdt
            s, _ = kgbdt.gbdt_score(hists, m.feat, m.thr, m.leaf, m.base,
                                    vc=self.vc)
            return s

        def ref():
            if self.head == "svm":
                return kref.svm_decision_ref(hists, self.w, self.b)
            m = self.gbdt
            return kref.gbdt_scores_ref(hists, m.feat, m.thr, m.leaf, m.base)

        return self._run({"fused": fused, "ref": ref}, mode, hists.shape,
                         jnp.dtype(hists.dtype).name, "classify_score")

    def leaf_indices(self, hists: Array, *,
                     mode: str | None = None) -> Array:
        """GBDT head only: per-tree leaf indices (B, T) i32 — the exact
        fused-vs-ref identity the oracle contract pins."""
        if self.head != "gbdt":
            raise ValueError("ClassifyPlan.leaf_indices: head is not 'gbdt'")
        m = self.gbdt

        def fused():
            faultinject.maybe_raise("lowering_error", site="classify:fused")
            _, li = kgbdt.gbdt_score(hists, m.feat, m.thr, m.leaf, m.base,
                                     vc=self.vc)
            return li

        def ref():
            return kref.gbdt_leaf_ref(hists, m.feat, m.thr)

        return self._run({"fused": fused, "ref": ref}, mode, hists.shape,
                         jnp.dtype(hists.dtype).name, "classify_score")

    def classify(self, hists: Array, *, mode: str | None = None) -> Array:
        """Histograms -> predicted labels (B,) i32."""
        s = self.scores(hists, mode=mode)
        return jnp.argmax(s, axis=1).astype(jnp.int32)

    def __call__(self, descs: Array, valids: Array, *,
                 mode: str | None = None) -> dict:
        """The whole tail: descriptors -> {"hist", "scores", "label"}."""
        h = self.histograms(descs, valids, mode=mode)
        s = self.scores(h, mode=mode)
        return {"hist": h, "scores": s,
                "label": jnp.argmax(s, axis=1).astype(jnp.int32)}


# a pytree, so a jitted program takes the model's arrays as an argument
# instead of baking them in as constants: one compile serves any weights
jax.tree_util.register_dataclass(
    ClassifyPlan, data_fields=["centroids", "w", "b", "gbdt"],
    meta_fields=["n_classes", "head", "vc", "mode", "ladder", "normalize"])


def build_plan(model, config: PipelineConfig | None = None) -> ClassifyPlan:
    """Bind a trained model to a ClassifyPlan using the config's
    classifier knobs (classify_mode / classify_ladder / vc).

    Dispatches on the model artifacts: an SVM model carries a ``svm``
    dict ({"w", "b"}), a GBDT model carries a ``gbdt`` `GbdtModel` —
    both carry ``centroids`` and ``n_classes`` (`cv.pipeline.BowSvmModel`
    / `BowGbdtModel`)."""
    cfg = config if config is not None else PipelineConfig()
    has_svm = getattr(model, "svm", None) is not None
    has_gbdt = getattr(model, "gbdt", None) is not None
    if not (has_svm or has_gbdt):
        raise ValueError(f"build_plan: {type(model).__name__} carries neither "
                         "an 'svm' dict nor a 'gbdt' GbdtModel")
    common = dict(centroids=model.centroids, n_classes=model.n_classes,
                  vc=cfg.vc, mode=cfg.classify_mode,
                  ladder=cfg.classify_ladder)
    if has_svm:
        return ClassifyPlan(head="svm", w=model.svm["w"], b=model.svm["b"],
                            **common)
    return ClassifyPlan(head="gbdt", gbdt=model.gbdt, **common)
