"""Shared benchmark utilities: timing (paper methodology: best of N),
structural metrics for Pallas rungs, the per-chip peaks table, table
printing."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

ART = os.path.join(os.path.dirname(__file__), "artifacts", "bench")
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RESULTS_PATH = os.path.join(REPO_ROOT, "BENCH_results.json")

# Published per-chip peaks keyed by `jax.Device.device_kind`.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}
TARGET_KIND = "TPU v5 lite"     # the chip the structural estimates model


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`; a device that is
    not in the table is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def best_of(fn, *args, n: int = 3, warmup: int = 1):
    """Paper methodology: several runs, shortest time (jit-warm first)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def time_stats(fn, *args, n: int = 5, warmup: int = 1):
    """best + median wall-clock over n jit-warm runs (machine-readable)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return {"best_s": ts[0], "median_s": ts[len(ts) // 2], "n": n}


# ---------------------------------------------------------------------------
# Cross-PR perf trajectory: every bench records entries here; flush_results()
# merges them into BENCH_results.json at the repo root.  The latest run's
# fields stay at the top level (tooling reads them directly); every run is
# ALSO appended to a `history` list keyed by git SHA + date, so the
# trajectory survives reruns (it used to be overwritten) and the CI perf
# gate (benchmarks/perf_gate.py) can diff against the previous entry.
# ---------------------------------------------------------------------------

_RESULTS: dict = {}

HISTORY_CAP = 50           # keep the last N runs


def record_result(bench: str, entry) -> None:
    _RESULTS.setdefault(bench, []).append(entry)


def git_sha() -> str:
    try:
        import subprocess
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            text=True, stderr=subprocess.DEVNULL).strip()
    except Exception:
        return "unknown"


# fields that identify "the same measurement" across runs (shapes differ
# between --quick and full passes, and a MODE=window run's fused_speedup is
# a different measurement than a both-plan run's; only like-for-like rows
# are compared — so a deliberate window-only pass can never trip the
# regression gate against a both-mode entry, or mask one).  `modes_timed`
# is the *requested* knob, not the measured winner: keying on the winner
# would change the row identity exactly when a plan regresses enough to
# flip it, blinding the gate at the worst moment.
ROW_KEYS = ("batch", "image", "resolution", "chain", "kernel", "size",
            "case", "dtype", "n_scales", "n_octaves", "modes_timed")


def row_key(row: dict) -> tuple:
    return tuple((k, row[k]) for k in ROW_KEYS if k in row)


def match_row(rows, key):
    for r in rows or []:
        if row_key(r) == key:
            return r
    return None


def print_delta(data: dict) -> None:
    """Delta of every fused_speedup-style metric vs the previous history
    entry that measured the same row (bench + shape)."""
    hist = data.get("history", [])
    if len(hist) < 2:
        print("\n(perf delta: no previous history entry to diff against)")
        return
    cur = hist[-1]
    print("\n### Perf delta vs previous run "
          f"({hist[-2]['sha']} {hist[-2]['date']})\n")
    any_row = False
    for bench, rows in sorted(cur.get("results", {}).items()):
        for row in rows:
            key = row_key(row)
            prev_row = None
            for entry in reversed(hist[:-1]):
                prev_row = match_row(entry.get("results", {}).get(bench), key)
                if prev_row:
                    break
            if not prev_row:
                continue
            for metric in ("fused_speedup", "fused_best_s"):
                if metric in row and metric in prev_row:
                    a, b = prev_row[metric], row[metric]
                    arrow = "+" if b >= a else "-"
                    print(f"  {bench} {dict(key)}: {metric} "
                          f"{a} -> {b} ({arrow})")
                    any_row = True
                    break
    if not any_row:
        print("  (no matching rows in history)")


def flush_results(path: str = RESULTS_PATH, *,
                  amend_same_sha: bool = False) -> str | None:
    """Merge recorded rows into BENCH_results.json + append one history
    entry.  ``amend_same_sha=True`` folds this process's rows into the
    LAST history entry when it carries the same git SHA instead of
    appending a second entry — two bench processes in one CI run (e.g.
    pipeline_bench then classify_bench) must look like ONE run to the
    perf gate, or rule 3 would find the first process's entry at
    hist[-2] and self-compare, masking real regressions."""
    if not _RESULTS:          # nothing measured: don't (re)write the file
        return None
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            data = {}
    data.update(_RESULTS)
    data["_meta"] = {"written_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
                     "backend": jax.default_backend()}
    sha = git_sha()
    hist = data.get("history", [])
    if amend_same_sha and hist and hist[-1].get("sha") == sha \
            and sha != "unknown":
        merged = dict(hist[-1].get("results", {}))
        merged.update(_RESULTS)
        hist[-1] = {**hist[-1],
                    "date": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "results": merged}
        data["history"] = hist[-HISTORY_CAP:]
    else:
        entry = {"sha": sha,
                 "date": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 "backend": jax.default_backend(),
                 "results": dict(_RESULTS)}
        data["history"] = (hist + [entry])[-HISTORY_CAP:]
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    return path


# fused-vs-unfused timing: one pallas_call over all planes of a (B, H, W, C)
# batch vs one launch per channel per image (the old wrapper structure)
FUSION_BATCH, FUSION_CROP = 4, (256, 512, 3)


def fusion_batch(stream):
    H, W, C = FUSION_CROP
    return jnp.stack([stream.image((H, W), channels=C, seed=b)
                      for b in range(FUSION_BATCH)])


def fused_vs_unfused(batch, op_fn, n: int = 3):
    """op_fn maps a (..., H, W[, C]) image -> same shape, via the fused path."""
    t_fused = time_stats(op_fn, batch, n=n)
    def unfused(x):
        return jnp.stack([jnp.stack([op_fn(x[b, :, :, c])
                                     for c in range(x.shape[-1])], axis=-1)
                          for b in range(x.shape[0])])
    t_unf = time_stats(unfused, batch, n=n)
    return t_fused, t_unf


def kernel_structure(vc, img_shape, *, halo: int, widen: bool, extra_bytes_per_step: int = 0):
    """Structural metrics of a band kernel at a given block width (the
    TPU-side evidence for the paper's claim: wider blocks => fewer grid
    steps / DMA issues, larger VMEM working set).  `est_hbm_s` is the
    HBM-bound time on one TARGET_KIND chip, from its published
    bandwidth: an estimate, not a measurement."""
    H, W = img_shape[:2]
    rows = vc.rows(jnp.uint8)
    wp = W + 2 * halo
    wp += (-wp) % vc.lane
    n_bands = -(-H // rows)
    in_bytes = (rows + 2 * halo) * wp            # one overlapping u8 window
    acc_bytes = (rows + 2 * halo) * wp * (4 if widen else 1) + rows * wp * (4 if widen else 1)
    vmem = 2 * (in_bytes + acc_bytes) + extra_bytes_per_step   # double-buffered
    hbm = H * wp + H * wp                        # read + write once (u8)
    return {
        "lmul": vc.lmul,
        "grid_steps": n_bands,
        "vmem_bytes": vmem,
        "vmem_ok": vmem <= vc.vmem_budget,
        "dma_per_step_bytes": in_bytes,
        "est_hbm_s": hbm / peaks(TARGET_KIND)["hbm_bytes_per_s"],
    }


def save_json(name: str, obj):
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


def print_table(title: str, headers: list[str], rows: list[list]):
    print(f"\n### {title}\n")
    widths = [max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
              for i, h in enumerate(headers)]
    line = " | ".join(str(h).ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-+-".join("-" * w for w in widths))
    for r in rows:
        print(" | ".join(str(c).ljust(w) for c, w in zip(r, widths)))
