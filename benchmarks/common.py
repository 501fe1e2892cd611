"""Shared benchmark harness pieces.

Every benchmark reproduces one paper figure/table at CPU-feasible scale
(reductions documented in EXPERIMENTS.md).  The latency axis always comes
from the paper-faithful Eqns 28-40 model with Table-I resources on the
FULL VGG-16/ResNet-18 profiles; only the accuracy axis runs reduced-width
models on the synthetic CIFAR-like data.
"""
from __future__ import annotations

import datetime
import glob
import hashlib
import os
import platform
import socket
import subprocess
import sys
import time

_TCMALLOC_GLOBS = (
    "/usr/lib/*/libtcmalloc_minimal.so*",
    "/usr/lib/*/libtcmalloc.so*",
    "/usr/lib/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
)


def _find_tcmalloc() -> str:
    for pat in _TCMALLOC_GLOBS:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[0]
    return ""


def setup_harness() -> str:
    """Process-level perf harness: the allocator.

    Preloads tcmalloc when the machine has it (the glob no-ops
    otherwise).  Must run BEFORE jax (or anything importing jax)
    initializes, which is why this module calls it at the very top.
    ``REPRO_HARNESS=0`` opts out entirely so the same drivers can
    measure the un-harnessed baseline; the returned
    state ("on"/"off") is recorded in every trajectory-CSV row.
    """
    if os.environ.get("REPRO_HARNESS", "1") == "0":
        return "off"
    lib = _find_tcmalloc()
    if lib and lib not in os.environ.get("LD_PRELOAD", ""):
        if os.environ.get("_REPRO_REEXEC") != "1":
            # LD_PRELOAD only takes effect at process start: re-exec
            # once (guarded so a failed preload cannot loop forever)
            os.environ["_REPRO_REEXEC"] = "1"
            os.environ["LD_PRELOAD"] = (
                os.environ.get("LD_PRELOAD", "") + ":" + lib
            ).strip(":")
            os.environ.setdefault(
                "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", str(15 << 30)
            )
            os.execv(sys.executable, [sys.executable] + sys.argv)
    return "on"


HARNESS = setup_harness()

from repro.utils.cache import enable_compilation_cache  # noqa: E402

# every figure run compiles the same small executables; cache them on disk
# so repeated runs skip compilation (JAX_COMPILATION_CACHE_DIR places it)
enable_compilation_cache()

from repro.api import ExperimentSpec, Session  # noqa: E402
from repro.config import get_config, SFLConfig  # noqa: E402
from repro.core.profiles import model_profile  # noqa: E402
from repro.core import baselines  # noqa: E402

OUT_DIR = os.environ.get("BENCH_OUT", "experiments/bench")

POLICIES = ["hasfl", "rbs+hams", "habs+rms", "rbs+rms", "rbs+rhams"]


def full_profile(arch: str = "vgg16-cifar"):
    return model_profile(get_config(arch))


def make_spec(
    *, n_clients=8, iid=False, agg_interval=15, lr=0.05,
    n_train=1200, n_test=300, seed=0, arch="vgg9-cifar-small",
    engine=None, sfl_overrides=None, **overrides
) -> ExperimentSpec:
    """The benchmark harness's historical `make_sim` wiring, as a spec.

    ``sfl_overrides`` reaches the remaining `SFLConfig` knobs (server
    resources, clip, priors) the figure sweeps scale — e.g. the fig7b
    ``server_flops`` axis."""
    return ExperimentSpec(
        arch=arch, n_clients=n_clients,
        partition="iid" if iid else "noniid-shards",
        n_train=n_train, n_test=n_test, seed=seed, engine=engine,
        sfl=SFLConfig(n_devices=n_clients, agg_interval=agg_interval,
                      lr=lr, **(sfl_overrides or {})),
        **overrides)


def make_sim(
    *, n_clients=8, iid=False, agg_interval=15, lr=0.05,
    n_train=1200, n_test=300, seed=0, arch="vgg9-cifar-small",
    n_classes=10, vectorized=True, engine=None
):
    """Build (simulator, optimizer) through `repro.api.Session`.

    ``engine=None`` auto-picks: the round-scan engine for the default
    vectorized path (what every paper-figure driver wants — fastest and
    equivalent), the legacy loop when ``vectorized=False``.  Figure
    drivers that sweep policies themselves keep using this; grid-shaped
    sweeps should build `ExperimentSpec`s (see `make_spec`) and go
    through `Session.run_grid`.
    """
    if engine is None:
        engine = "scan" if vectorized else "legacy"
    sess = Session(
        make_spec(
            n_clients=n_clients, iid=iid, agg_interval=agg_interval, lr=lr,
            n_train=n_train, n_test=n_test, seed=seed, arch=arch,
            engine=engine,
        )
    )
    return sess.sim, sess.optimizer


def run_spec_grid(figure, specs, *, runner="auto", out_dir=None):
    """Dispatch one figure's spec grid; returns ``(results, wall_s)``.

    The single entry point every figure driver funnels through (the
    one-command reproduction, DESIGN.md §13): compatible cells —
    policies x scenarios x *seeds*, since `grid_key` no longer pins the
    seed — batch into vmapped mega-runs per `Session.run_grid`, and the
    exact specs are committed next to the CSV as
    ``<out_dir>/<figure>.specs.json`` so the figure replays bit-for-bit.
    """
    from repro.api import Session, save_specs

    out_dir = out_dir or OUT_DIR
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.time()
    results = Session.run_grid(specs, runner=runner)
    wall = time.time() - t0
    save_specs(os.path.join(out_dir, f"{figure}.specs.json"), specs)
    print(
        f"[{figure}] {len(specs)} cells via runner={runner} "
        f"in {wall:.1f}s", flush=True
    )
    return results, wall


def band_cols(cols):
    """Error-band column names for ``cols``: std/min/max per column.

    Appended LAST to a driver's header (after the value columns) so the
    CSVs extend their old schema — `append_csv` prefix-migrates any
    retained history by padding old rows empty.
    """
    out = []
    for c in cols:
        out.extend([f"{c}_std", f"{c}_min", f"{c}_max"])
    return out


def seed_curve_rows(series, results_by_seed, cols):
    """Eval-trajectory CSV rows for one series: per-seed + mean.

    ``series`` is the row's leading label columns (list), ``cols`` the
    `SimResult` attribute names to emit.  Every seed's cells share the
    eval schedule (same spec rounds/eval_every), so the mean curve is
    the elementwise mean — the figure's plotted line.  Mean rows carry
    the seed spread in trailing ``band_cols(cols)`` columns (std/min/max
    over the per-seed values at that eval point); per-seed rows — which
    stay in the CSV and are what the bands are computed from — pad those
    columns empty.
    """
    import numpy as np

    series = list(series)
    seeds = sorted(results_by_seed)
    results = [results_by_seed[s] for s in seeds]
    rounds = results[0].rounds
    for r in results[1:]:
        if r.rounds != rounds:
            raise ValueError("seed cells must share the eval schedule")
    pad = [""] * (3 * len(cols))
    rows = []
    for s, r in zip(seeds, results):
        for k, t in enumerate(rounds):
            rows.append(
                series + [s, t] + [getattr(r, c)[k] for c in cols] + pad)
    stacks = [np.asarray([getattr(r, c) for r in results]) for c in cols]
    for k, t in enumerate(rounds):
        band = []
        for st in stacks:
            band.extend([float(st[:, k].std()), float(st[:, k].min()),
                         float(st[:, k].max())])
        rows.append(
            series + ["mean", t]
            + [float(st[:, k].mean()) for st in stacks] + band)
    return rows


def seed_summary_rows(series, results_by_seed, fns):
    """Scalar-summary CSV rows for one series: per-seed + mean.

    ``fns``: list of ``SimResult -> float`` extractors (final acc,
    converged time, ...).  Mean rows append std/min/max bands per
    extractor (same trailing-column convention as `seed_curve_rows`)."""
    import numpy as np

    series = list(series)
    seeds = sorted(results_by_seed)
    vals = np.asarray(
        [[fn(results_by_seed[s]) for fn in fns] for s in seeds], float)
    pad = [""] * (3 * len(fns))
    rows = [series + [s] + list(v) + pad for s, v in zip(seeds, vals)]
    band = []
    for j in range(len(fns)):
        band.extend([float(vals[:, j].std()), float(vals[:, j].min()),
                     float(vals[:, j].max())])
    rows.append(
        series + ["mean"] + [float(x) for x in vals.mean(0)] + band)
    return rows


def run_policy(sim, opt, name, rounds, eval_every=10):
    def policy(s, prng):
        return baselines.policy(name, opt, prng)

    t0 = time.time()
    res = sim.run(policy, rounds=rounds, eval_every=eval_every)
    wall = time.time() - t0
    return res, wall


def robust_theta(opt, b, cuts) -> float:
    """Theta with an adaptive epsilon: policies whose variance/drift terms
    exceed eps (random small batches) would never reach eps by the bound
    (theta = inf); the paper instead *measures* their (much longer)
    converged time.  We report the bound-latency at the tightest accuracy
    the policy CAN reach (1.05x its asymptotic floor), applied uniformly to
    all policies so comparisons stay fair."""
    import numpy as _np
    l_c = int(_np.max(cuts))
    floor = opt.conv.variance_term(b) + opt.conv.drift_term(l_c)
    eps_eff = max(opt.sfl.epsilon, 1.05 * floor)
    r = opt.conv.rounds_needed(b, l_c, eps_eff)
    return r * opt.lat.per_round_effective(b, cuts)


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def save_csv(path: str, header: list, rows: list) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")


def git_sha() -> str:
    """Short git SHA of the working tree (trajectory-row provenance);
    empty string outside a repo so benchmarks still run from tarballs."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))
        )
        return out.stdout.strip() if out.returncode == 0 else ""
    except (OSError, subprocess.SubprocessError):
        return ""


def now_iso() -> str:
    fmt = "%Y-%m-%dT%H:%M:%SZ"
    return datetime.datetime.now(datetime.timezone.utc).strftime(fmt)


def runner_id() -> str:
    """Stable hostname+CPU fingerprint for trajectory-CSV rows.

    Absolute-ms columns are only comparable between rows measured on the
    same box; the perf gate currently fails solely on the box-invariant
    speedup ratios, and this column is what will later let it match
    absolute-ms rows same-box.  Comma-free so it drops straight into the
    CSVs.
    """
    cpu = platform.processor() or platform.machine() or "unknown"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    fp = hashlib.sha1(f"{cpu}|{os.cpu_count()}".encode()).hexdigest()[:8]
    host = socket.gethostname().split(".")[0].replace(",", "_")
    return f"{host}-{fp}"


# The sim_speed.csv trajectory schema (owned here so both the engine
# micro-benchmark and the figure lane append compatible rows).  The
# PR-8 ``figure``/``wall_s`` columns go LAST — pre-existing rows are
# prefix-migrated (padded empty) by append_csv: engine rows leave them
# empty, figure-lane rows leave the engine ms/ratio columns empty, and
# the perf gate treats ``wall_s`` as warn-only (figure walls swing with
# cell counts and CI tenancy; the hard gate stays on the engine ratios).
# ``peak_mem_mb`` (mesh N-scaling rows, DESIGN.md §15) extends the
# schema again — same append-LAST prefix migration.
SIM_SPEED_HEADER = [
    "config", "n_clients", "loop_ms", "vectorized_ms", "scan_ms",
    "vec_speedup", "scan_speedup", "git_sha", "timestamp",
    "runner_id", "harness", "figure", "wall_s", "peak_mem_mb"
]


def record_figure_walls(walls, *, quick=False, out_dir=None) -> None:
    """Append figure-lane wall-time rows to the sim_speed trajectory.

    ``walls``: list of ``(figure, wall_s)``.  Rows carry the same
    git_sha/runner_id/harness provenance as the engine rows and key as
    ``config=fig-<name>[-quick]`` so quick (CI) and full walls never
    compare against each other.
    """
    out = os.path.join(out_dir or OUT_DIR, "sim_speed.csv")
    sha, ts, rid = git_sha(), now_iso(), runner_id()
    suffix = "-quick" if quick else ""
    rows = [
        [f"fig-{name}{suffix}", "", "", "", "", "", "",
         sha, ts, rid, HARNESS, name, round(wall, 1), ""]
        for name, wall in walls
    ]
    append_csv(out, SIM_SPEED_HEADER, rows)


def append_csv(path: str, header: list, rows: list) -> None:
    """Append rows, migrating or rotating the file when the schema moved.

    Used by trajectory files (``sim_speed.csv``): every run adds rows so
    the perf history across PRs stays visible instead of being clobbered.
    When the on-disk header is a *prefix* of the new one (columns were
    appended — e.g. the git_sha/timestamp provenance columns), old rows
    are kept and padded with empty fields, so the whole trajectory stays
    parseable under the new schema.  On an incompatible change the old
    file is preserved as ``<path>.old`` rather than silently deleted.
    """
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    head = ",".join(header)
    keep = False
    if os.path.exists(path):
        with open(path) as f:
            old_lines = f.read().splitlines()
        old_head = old_lines[0].strip() if old_lines else ""
        keep = old_head == head
        old_fields = old_head.split(",")
        if not keep and old_fields == header[:len(old_fields)]:
            # schema extension: pad historical rows to the new width
            pad = "," * (len(header) - len(old_fields))
            with open(path, "w") as f:
                f.write(head + "\n")
                for line in old_lines[1:]:
                    if line.strip():
                        f.write(line + pad + "\n")
            keep = True
        elif not keep:
            bak = path + ".old"
            k = 1
            while os.path.exists(bak):
                bak = f"{path}.old{k}"
                k += 1
            os.replace(path, bak)
    with open(path, "a" if keep else "w") as f:
        if not keep:
            f.write(head + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
