"""Bring-up check: the ERM trainer's main path on one TPU chip.

Drives ``repro.api.plan()`` -> ``execute()`` in this one process, at the
scale of the paper's benchmark corpora, generated from a seed:

* dense resident: a HIGGS-shaped corpus (UCI/LIBSVM HIGGS, 11,000,000 rows
  x 28 features + label, float32), placement and kernel left to the
  planner, batch 1000 (paper Table 2), 2 epochs: SAGA/constant under
  systematic (block kernel) and random (rows kernel) sampling, SVRG/line
  search under cyclic sampling, and the systematic SAGA run again with
  ``kernel="eager"``.  The planner must pick ``resident-fused`` and the
  compiled epoch program must hold the Pallas kernel (``tpu_custom_call``).
* dense streamed: the same corpus with ``placement="streamed"``, SAGA under
  random and systematic sampling.
* CSR streamed: an rcv1-shaped corpus (LIBSVM rcv1.binary: 20,242 rows x
  47,236 features, about 0.16% nonzeros), MBSGD under random and
  systematic sampling, batch 500.

Every run's final objective is checked against a plain float32
``jax.numpy`` evaluation of the same ``ERMProblem`` objective at the
returned weights, with matmul precision pinned to highest, and must be
below the objective at w=0.  One line per run gives its phase, backend,
kernel, objective, reference, relative difference, wall and compile
seconds and the device's peak memory; the last line is
``{"ok": true, "device": {...}}``.  Any failed run makes the exit code 1;
a missing TPU makes it 2.

``--four-chips`` runs only the data-parallel path: the dense streamed SAGA
systematic run on a 4-device mesh under ``reduction="gather"`` and
``"psum"``, against the same spec on one device.

``--rehearse`` runs every phase at tiny sizes on whatever backend JAX has
(``JAX_PLATFORMS=cpu``, kernels in interpret mode); it never prints
``"ok": true``.

    python chip_smoke.py                      # one TPU chip
    python chip_smoke.py --four-chips         # one host with four
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import inspect
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.api import (EAGER, FUSED, GATHER, PSUM, RESIDENT,  # noqa: E402
                       RESIDENT_EAGER, RESIDENT_FUSED, SHARDED_STREAMED,
                       SPARSE_CSR, STREAMED, STREAMED_EAGER, DataSource,
                       ExperimentSpec, execute, plan)
from repro.core import solvers  # noqa: E402
from repro.data import dataset, sparse  # noqa: E402
from repro.distributed.sharding import make_staging_put  # noqa: E402

CORPUS_DIR = ROOT / "artifacts" / "chip_smoke"
# (rows, features[, density]) at full size and for --rehearse
HIGGS = {"full": (11_000_000, 28), "rehearse": (16_384, 28)}
RCV1 = {"full": (20_242, 47_236, 0.0016), "rehearse": (1_024, 47_236, 0.0016)}

# The run reports its objective through the device's default matmul, one
# bf16 pass on a TPU: each product x_j w_j carries up to 2^-8 relative
# error.  At HIGGS margins that is ~1e-3 per row, unbiased to first order,
# so over >= 20k rows the mean loss moves by < 1e-5 of itself; 1e-4 bounds it.
REF_RTOL = 1e-4
# Fused gradients are float32 VPU sums, eager ones default-precision
# matmuls: each step's gradient differs by bf16 rounding, and the two
# trajectories drift apart by that noise over 22,000 steps.  The solver is
# contractive, so the end points stay within the noise ball, and the
# objective difference is the gradient times that distance: 1e-3 of f.
FUSED_EAGER_RTOL = 1e-3
# psum reorders each batch reduction across 4 devices: float32 rounding
# (1e-7 relative) per step, which the contractive solver keeps at rounding
# scale; 1e-5 of f leaves two orders of magnitude.
PSUM_RTOL = 1e-5


def _stamp(generator, **shape) -> dict:
    """What a cached corpus must match to be reused: its shape, seed and
    the generator's source."""
    src = inspect.getsource(generator).encode()
    return {"generator": generator.__qualname__,
            "source_sha256": hashlib.sha256(src).hexdigest(), **shape}


def _fresh(path: Path, stamp: dict, make) -> Path:
    """Generate into ``path`` unless a finished corpus with this stamp is
    there; the stamp is written last, so a half-written corpus never
    matches."""
    stamp_path = path.parent / (path.name + ".stamp.json")
    if stamp_path.exists() and json.loads(stamp_path.read_text()) == stamp:
        return path
    stamp_path.unlink(missing_ok=True)
    t0 = time.perf_counter()
    make()
    stamp_path.write_text(json.dumps(stamp))
    print(f"# generated {path.name} in {time.perf_counter() - t0:.1f} s",
          flush=True)
    return path


def higgs_corpus(size: str, seed: int) -> Path:
    rows, feats = HIGGS[size]
    path = CORPUS_DIR / f"higgs_{rows}x{feats}_s{seed}.bin"
    stamp = _stamp(dataset.synth_erm_corpus, rows=rows, features=feats,
                   seed=seed)
    return _fresh(path, stamp, lambda: dataset.synth_erm_corpus(
        path, rows=rows, features=feats, seed=seed))


def rcv1_corpus(size: str, seed: int) -> Path:
    rows, feats, density = RCV1[size]
    path = CORPUS_DIR / f"rcv1_{rows}x{feats}_s{seed}.csr"
    stamp = _stamp(sparse.synth_sparse_classification, rows=rows,
                   features=feats, density=density, seed=seed)
    return _fresh(path, stamp, lambda: sparse.synth_sparse_classification(
        path, rows=rows, features=feats, density=density, seed=seed))


# ---------------------------------------------------------------------------
# references: plain float32 jax.numpy objectives at highest matmul precision
# ---------------------------------------------------------------------------

def dense_reference(path: Path, problem, ws):
    """[(f(w), f(0)) for w in ws] over the whole dense corpus."""
    mm, meta = dataset.open_corpus(path)
    n = meta.row_dim - 1
    X = jnp.asarray(np.ascontiguousarray(mm[:, :n]))
    y = jnp.asarray(np.ascontiguousarray(mm[:, n]))
    with jax.default_matmul_precision("highest"):
        f = jax.jit(problem.objective)
        f0 = float(f(jnp.zeros(n, jnp.float32), X, y))
        out = [(float(f(jnp.asarray(w), X, y)), f0) for w in ws]
    del X, y
    return out


def csr_reference(path: Path, problem, ws):
    """[(f(w), f(0)) for w in ws] from the CSR arrays themselves: margins
    are a segment sum of values * w[indices] over each row."""
    csr = sparse.open_csr_corpus(path)
    indptr = np.asarray(csr.indptr)
    rows = jnp.asarray(np.repeat(np.arange(csr.rows, dtype=np.int32),
                                 np.diff(indptr)))
    cols = jnp.asarray(np.asarray(csr.indices))
    vals = jnp.asarray(np.asarray(csr.values))
    y = jnp.asarray(np.asarray(csr.labels))

    @jax.jit
    def f(w):
        z = jax.ops.segment_sum(vals * w[cols], rows, num_segments=y.shape[0])
        return problem.mean_margin_loss(z, y) + 0.5 * problem.reg * (w @ w)

    with jax.default_matmul_precision("highest"):
        f0 = float(f(jnp.zeros(csr.features, jnp.float32)))
        return [(float(f(jnp.asarray(w))), f0) for w in ws]


# ---------------------------------------------------------------------------
# running and reporting
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def _peak_bytes(device, key: str = "peak_bytes_in_use") -> int:
    """A peak from the device's memory_stats(); -1 where it reports none.
    ``peak_bytes_in_use`` counts live buffers only; the temporaries of a
    running executable are held apart, in ``peak_bytes_reserved``."""
    stats = device.memory_stats() or {}
    return int(stats.get(key, -1))


@dataclasses.dataclass
class Run:
    phase: str
    name: str
    backend: str = ""
    kernel: str = ""
    objective: float = math.nan
    w: object = None
    wall_s: float = math.nan
    compile_s: float = math.nan
    peak_bytes: int = -1
    peak_reserved: int = -1
    reference: float = math.nan
    f0: float = math.nan
    note: str = ""
    error: str = ""

    @property
    def rel_diff(self) -> float:
        return abs(self.objective - self.reference) / abs(self.reference)

    def check(self):
        """The run's own checks against its reference; raises on a miss."""
        if not math.isfinite(self.objective):
            raise AssertionError(f"objective {self.objective} is not finite")
        if not self.rel_diff <= REF_RTOL:
            raise AssertionError(
                f"objective {self.objective!r} is {self.rel_diff:.3e} from "
                f"the reference {self.reference!r} (bound {REF_RTOL:g})")
        if not self.objective < self.f0:
            raise AssertionError(f"objective {self.objective!r} is not below "
                                 f"f(0) = {self.f0!r}")

    def line(self) -> str:
        status = "FAIL " + self.error if self.error else "ok"
        return (f"{self.phase:<16} {self.name:<26} backend={self.backend} "
                f"kernel={self.kernel} objective={self.objective!r} "
                f"reference={self.reference!r} rel_diff={self.rel_diff:.3e} "
                f"f0={self.f0!r} wall_s={self.wall_s:.2f} "
                f"compile_s={self.compile_s:.2f} "
                f"peak_bytes_in_use={self.peak_bytes} "
                f"peak_bytes_reserved={self.peak_reserved} {self.note} "
                f"[{status}]")


class Smoke:
    def __init__(self, args):
        self.args = args
        self.size = "rehearse" if args.rehearse else "full"
        self.clock = CompileClock()
        self.failed = []

    def fail(self, what: str, exc: BaseException):
        traceback.print_exc()
        self.failed.append(f"{what}: {type(exc).__name__}: {exc}")

    def execute(self, phase, name, spec, want_backend) -> Run:
        """plan + execute one spec; a failure is recorded, not raised."""
        run = Run(phase, name)
        c0, t0 = self.clock.total, time.perf_counter()
        try:
            p = plan(spec)
            run.backend, run.kernel = p.backend, p.kernel
            if p.backend != want_backend:
                raise AssertionError(f"planned {p.backend}, wanted "
                                     f"{want_backend}: {p.why}")
            res = execute(p)
            run.objective, run.w = float(res.objective), np.asarray(res.w)
            run.wall_s = time.perf_counter() - t0
            run.compile_s = self.clock.total - c0
            run.peak_bytes = _peak_bytes(jax.devices()[0])
            run.peak_reserved = _peak_bytes(jax.devices()[0],
                                            "peak_bytes_reserved")
            run.note = (f"h2d_bytes={res.stats.bytes_staged} "
                        f"h2d_bytes_per_device="
                        f"{res.stats.h2d_bytes_per_device}")
            if p.placement == RESIDENT and not self.args.rehearse:
                run.note += " " + self.epoch_program(p)
        except Exception as e:      # the boundary: report, keep going
            run.error = f"{type(e).__name__}: {e}"
            self.fail(f"{phase}/{name}", e)
        return run

    @staticmethod
    def epoch_program(p) -> str:
        """Compile the resident epoch program of this plan; a fused plan's
        must hold the Pallas kernel.  Returns its memory analysis."""
        n, l = p.features, p.rows
        fn = solvers.make_resident_epoch_fn(p.spec.problem, p.cfg,
                                            p.scheme_name, p.spec.batch_size)
        state = jax.eval_shape(
            lambda w: solvers.init_state(p.cfg.solver, w, p.num_batches),
            jax.ShapeDtypeStruct((n,), jnp.float32))
        compiled = fn.func.lower(
            *fn.args, state, jax.ShapeDtypeStruct((l, n), jnp.float32),
            jax.ShapeDtypeStruct((l,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            **fn.keywords).compile()
        kernel = "tpu_custom_call" in compiled.as_text()
        if p.backend == RESIDENT_FUSED and not kernel:
            raise AssertionError("resident-fused epoch program has no "
                                 "tpu_custom_call")
        ma = compiled.memory_analysis()
        x_layout = compiled.input_formats[0][1]    # (state, X, y, key)
        return (f"tpu_custom_call={kernel} epoch_program_argument_bytes="
                f"{ma.argument_size_in_bytes} epoch_program_temp_bytes="
                f"{ma.temp_size_in_bytes} "
                f"X_layout={x_layout.layout.major_to_minor}"
                f"{x_layout.layout.tiling}")

    def references(self, runs, ref_fn, path, problem):
        done = [r for r in runs if not r.error]
        try:
            for r, (f, f0) in zip(done, ref_fn(path, problem,
                                               [r.w for r in done])):
                r.reference, r.f0 = f, f0
                try:
                    r.check()
                except AssertionError as e:
                    r.error = str(e)
                    self.failed.append(f"{r.phase}/{r.name}: {e}")
        except Exception as e:
            for r in done:
                r.error = r.error or "no reference"
            self.fail("reference", e)
        for r in runs:
            print(r.line(), flush=True)

    # ---- one chip ---------------------------------------------------------

    def dense(self):
        rehearse = self.args.rehearse
        path = higgs_corpus(self.size, self.args.seed)
        base = ExperimentSpec(data=DataSource.corpus(path), solver="saga",
                              batch_size=1000, epochs=2, seed=self.args.seed)
        # off-TPU the planner keeps the interpreter off the auto path, so
        # the rehearsal forces the kernel it would pick on the chip
        fused = dict(kernel=FUSED, placement="resident") if rehearse else {}
        resident = [
            ("saga-systematic", dict(scheme="systematic", **fused),
             RESIDENT_FUSED),
            ("saga-random", dict(scheme="random", **fused), RESIDENT_FUSED),
            ("svrg-ls-cyclic", dict(solver="svrg", step_mode="line_search",
                                    scheme="cyclic", **fused),
             RESIDENT_FUSED),
            ("saga-systematic-eager", dict(scheme="systematic", kernel=EAGER,
                                           placement="resident"),
             RESIDENT_EAGER),
        ]
        runs = [self.execute("dense-resident", name,
                             dataclasses.replace(base, **kw), want)
                for name, kw, want in resident]
        runs += [self.execute("dense-streamed", f"saga-{scheme}",
                              dataclasses.replace(base, scheme=scheme,
                                                  placement=STREAMED),
                              STREAMED_EAGER)
                 for scheme in ("random", "systematic")]
        print(f"# memory_stats after the resident runs: "
              f"{jax.devices()[0].memory_stats()}", flush=True)
        self.references(runs, dense_reference, path, base.problem)
        fused_run, eager_run = runs[0], runs[3]
        if not (fused_run.error or eager_run.error):
            d = (abs(fused_run.objective - eager_run.objective)
                 / abs(eager_run.objective))
            dw = float(np.max(np.abs(fused_run.w - eager_run.w)))
            ok = d <= FUSED_EAGER_RTOL
            print(f"dense-resident   fused-vs-eager saga-systematic "
                  f"fused={fused_run.objective!r} "
                  f"eager={eager_run.objective!r} rel_diff={d:.3e} "
                  f"max_abs_dw={dw:.3e} bound={FUSED_EAGER_RTOL:g} "
                  f"[{'ok' if ok else 'FAIL'}]", flush=True)
            if not ok:
                self.failed.append(f"fused-vs-eager rel_diff {d:.3e}")

    def csr(self):
        path = rcv1_corpus(self.size, self.args.seed)
        base = ExperimentSpec(data=DataSource.corpus(path), solver="mbsgd",
                              batch_size=500, epochs=2, seed=self.args.seed)
        runs = [self.execute("csr-streamed", f"mbsgd-{scheme}",
                             dataclasses.replace(base, scheme=scheme),
                             SPARSE_CSR)
                for scheme in ("random", "systematic")]
        self.references(runs, csr_reference, path, base.problem)

    # ---- four chips -------------------------------------------------------

    def four_chips(self):
        devices = jax.devices()
        if len(devices) < 4:
            raise RuntimeError(f"--four-chips needs 4 devices, JAX sees "
                               f"{len(devices)}")
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        path = higgs_corpus(self.size, self.args.seed)
        base = ExperimentSpec(data=DataSource.corpus(path), solver="saga",
                              scheme="systematic", placement=STREAMED,
                              batch_size=1000, epochs=2, seed=self.args.seed)
        self.staged_share(path, mesh)
        one = self.execute("four-chips", "one-device", base, STREAMED_EAGER)
        meshed = {red: self.execute(
            "four-chips", f"mesh4-{red}",
            dataclasses.replace(base, mesh=mesh, reduction=red),
            SHARDED_STREAMED) for red in (GATHER, PSUM)}
        peaks = [_peak_bytes(d) for d in devices[:4]]
        self.references([one, *meshed.values()], dense_reference, path,
                        base.problem)
        print(f"four-chips       peak_bytes_in_use per device: {peaks}",
              flush=True)
        if one.error:
            return
        for red, r in meshed.items():
            if r.error:
                continue
            d = abs(r.objective - one.objective) / abs(one.objective)
            dw = float(np.max(np.abs(r.w - one.w)))
            same = bool(np.array_equal(r.w, one.w))
            ok = same or d <= PSUM_RTOL
            print(f"four-chips       {red}-vs-one-device "
                  f"objective_rel_diff={d:.3e} max_abs_dw={dw:.3e} "
                  f"weights_bitwise_equal={same} bound={PSUM_RTOL:g} "
                  f"[{'ok' if ok else 'FAIL'}]", flush=True)
            if not ok:
                self.failed.append(f"{red} vs one device: rel_diff {d:.3e}, "
                                   f"max |dw| {dw:.3e}")

    def staged_share(self, path: Path, mesh):
        """Stage one chunk through the mesh staging put, as the sharded
        streamed backend does, and report each device's share of it."""
        mm, meta = dataset.open_corpus(path)
        n, k, b = meta.row_dim - 1, 8, 1000
        rows = np.ascontiguousarray(mm[:k * b]).reshape(k, b, n + 1)
        host = (rows[..., :n], rows[..., n], np.arange(k, dtype=np.int32))
        put = make_staging_put(mesh, ((None, "batch", None), (None, "batch"),
                                      (None,)))
        for name, arr in zip(("X", "y", "js"), put(host)):
            share = {str(s.device.id): int(s.data.nbytes)
                     for s in arr.addressable_shards}
            print(f"four-chips       staged {name} {arr.shape} "
                  f"bytes_per_device={share}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device data-parallel path")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
              f"{dev.device_kind!r}); pass --rehearse for a CPU rehearsal",
              file=sys.stderr)
        return 2
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    print(f"# device {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" bytes_limit={limit}, compile cache {compile_cache.enable()}",
          flush=True)
    smoke = Smoke(args)
    phases = ([smoke.four_chips] if args.four_chips
              else [smoke.dense, smoke.csr])
    for phase in phases:
        try:
            phase()
        except Exception as e:      # the boundary: report, keep going
            smoke.fail(phase.__name__, e)
    if smoke.failed:
        print(f"# {len(smoke.failed)} failure(s): {smoke.failed}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print(f"# rehearsal passed on {dev.platform}: not a chip run")
        return 0
    count = 4 if args.four_chips else len(jax.devices())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
