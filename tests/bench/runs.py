"""Drive a whole harness run on the CPU at the tiny test configurations
of ``data/`` (the chip check skipped), optionally with the timed path
broken underneath."""
import json
import os

import jax.numpy as jnp

from bench import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

FAULTS = ("half_batch", "answer_altered")


def plant(monkeypatch, fault):
    """Break the program's pallas backend: ``half_batch`` leaves the
    second half of every batch out (it repeats the first half's answers),
    ``answer_altered`` shifts the rows of the first image's answer."""
    from repro.api import backends
    orig = backends.PallasBackend.apply

    def apply(self, plan, x, prep, **kw):
        y = orig(self, plan, x, prep, **kw)
        if fault == "half_batch":
            n = y.shape[0] // 2
            return y.at[n:2 * n].set(y[:n])
        return y.at[0].set(jnp.roll(y[0], 1, axis=0))
    monkeypatch.setattr(backends.PallasBackend, "apply", apply)


def run_cell(capsys, workload, *extra, seed=11):
    """(exit code, result line, stderr lines) of one tiny run."""
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0", *extra],
                  require_tpu=False,
                  bench_json=os.path.join(DATA, "BENCHMARK.json"))
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), \
        err.strip().splitlines()
