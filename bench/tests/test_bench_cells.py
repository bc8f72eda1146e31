"""Every cell's run at a small size on the CPU, harness and all, minus the
look for a chip: the program served through its scheduler (or fitted, or
assimilated) agrees with the plain reference; each fault a cell can have,
planted in the timed path, turns ``correct`` false; and the control (the
reference one precision step lower, in the program's place) reads at least
three times what the program reads. A cell on more than one chip runs in a
process of its own with as many host CPU devices."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run
from repro.core import api, online, ppic
from repro.parallel.runner import VmapRunner

SMALL = dict(n=320, machines=4, support=32, kernel_impl="jnp")
PARAMS = dict(pool=64, rate_per_s=200, clients=16, wave_rows=16, datasets=2)
SEED = 2**31 + 77
# the poisson cell's files stay in bench/ while the cell is out of
# BENCHMARK.json (PERF.md, open questions): its chips and metrics as they
# were listed
PARKED = {"aimpeak-ppic.poisson": (
    "aimpeak-ppic", 1, [("query_p99_ms", "ms"), ("setup_s", "s")],
    [("gen_late_ms.p99", "ms"), ("host_ms_per_flush.p99", "ms"),
     ("routed_g0_share", "%"), ("device_idle.p99", "%")])}
CELLS = [w["name"] for w in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"]] + sorted(PARKED)


@pytest.fixture
def float32():
    """The benchmark runs in float32; the repository's own tests turn
    float64 on for the whole process."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def file_cell(name: str) -> run.Cell:
    if name not in PARKED:
        return run.load_cell(name)
    config, chips, e2e, layer = PARKED[name]
    read = lambda path: json.loads((run.BENCH / path).read_text())
    entries = lambda pairs: [{"name": n, "unit": u} for n, u in pairs]
    return run.Cell(name, chips, read(f"workloads/{name}.json"),
                    read(f"configs/{config}.json"), entries(e2e),
                    entries(layer))


def small_cell(name: str, **config) -> run.Cell:
    cell = file_cell(name)
    cell.config = dict(cell.config, **{**SMALL, **config})
    cell.config["serve"] = dict(cell.config["serve"], max_batch=8)
    params = {k: PARAMS.get(k, v) for k, v in cell.workload["params"].items()}
    cell.workload = dict(cell.workload, params=params)
    return cell


def go(name: str, *, seed: int = SEED, trace: bool = False, cell=None,
       control: bool = False) -> dict:
    return run.run_cell(name, seed, 0.3, trace, require_tpu=False,
                        cell=cell or small_cell(name), control=control,
                        log=lambda s: None)


def on_devices(n: int, script: str) -> dict:
    """Run ``script`` in a fresh process that sees ``n`` host CPU devices
    (float32, as the benchmark runs); returns the JSON object its last line
    of output prints."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(run.ROOT),
                                           str(run.ROOT / "src")]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={n}").strip()
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=run.ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def go_cell(name: str) -> dict:
    """``go(name)``, on as many devices as the cell asks for."""
    chips = file_cell(name).chips
    if chips == 1:
        return go(name)
    return on_devices(chips, "import json\n"
                      "from bench.tests.test_bench_cells import go\n"
                      f"print(json.dumps(go({name!r})))")


@pytest.mark.parametrize("name", CELLS)
def test_cell_agrees_with_the_reference(name, float32):
    r = go_cell(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    e2e = {m["name"] for m in file_cell(name).end_to_end}
    assert set(r["metrics"]) == e2e and "setup_s" in e2e
    assert list(r)[-1] == "checks"
    for row in r["checks"].values():
        assert row["value"] <= row["limit"]


@pytest.mark.parametrize("name,host_metrics", [
    ("aimpeak-ppic.poisson", {"gen_late_ms.p99", "host_ms_per_flush.p99",
                              "routed_g0_share"}),
    ("sarcos-ppitc.closed", {"host_ms_per_flush.qps"}),
    ("sarcos-ppitc.waves", {"swap_ms.wave"})])
def test_traced_run_reads_its_host_metrics(name, host_metrics, float32):
    r = go(name, trace=True)
    assert r["correct"]
    assert host_metrics <= set(r["metrics"])
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


# -- faults planted in the timed path ----------------------------------------------

def _altered(mean, var):
    """One answer altered where it is produced."""
    return mean.at[0].add(1.0), var


def _half(mean, var):
    """Half of the batch left out: its rows answered with zeros."""
    h = mean.shape[0] // 2
    return mean.at[h:].set(0.0), var.at[h:].set(0.0)


@pytest.mark.parametrize("fault", [_altered, _half])
@pytest.mark.parametrize("name,plan,method", [
    ("aimpeak-ppic.poisson", ppic.PICServePlan, "routed_diag"),
    ("sarcos-ppitc.closed", api.ServePlan, "diag")])
def test_serving_fault_is_caught(name, plan, method, fault, float32,
                                 monkeypatch):
    real = getattr(plan, method)
    monkeypatch.setattr(plan, method,
                        lambda self, U, *a, **k: fault(*real(self, U, *a,
                                                             **k)))
    assert not go(name)["correct"]


def _ppitc_method(monkeypatch, fit):
    method = api.get("ppitc")
    monkeypatch.setitem(api.REGISTRY, "ppitc",
                        dataclasses.replace(method, fit=fit))
    return method.fit


def test_fit_returning_a_stale_state_is_caught(float32, monkeypatch):
    seen = {}

    def stale(kfn, params, X, y, **kw):
        # the state of the first fit, whatever the data
        if "state" not in seen:
            seen["state"] = real(kfn, params, X, y, **kw)
        return seen["state"]

    real = _ppitc_method(monkeypatch, stale)
    assert not go("sarcos-ppitc.fit")["correct"]


def test_fit_with_an_answer_altered_is_caught(float32, monkeypatch):
    def altered(kfn, params, X, y, **kw):
        state = real(kfn, params, X, y, **kw)
        return state._replace(w=state.w.at[0].add(1.0))

    real = _ppitc_method(monkeypatch, altered)
    assert not go("sarcos-ppitc.fit")["correct"]


def test_fit_with_half_the_data_left_out_is_caught(float32, monkeypatch):
    def half(kfn, params, X, y, *, S, runner):
        h = X.shape[0] // 2
        return real(kfn, params, X[:h], y[:h], S=S,
                    runner=VmapRunner(M=runner.num_machines // 2))

    real = _ppitc_method(monkeypatch, half)
    assert not go("sarcos-ppitc.fit")["correct"]


def test_fit_without_the_exchange_between_blocks_is_caught(float32,
                                                           monkeypatch):
    real = online._whitened_chol
    monkeypatch.setattr(online, "_whitened_chol",
                        lambda G, alive=None: real(G[:1]))
    assert not go("sarcos-ppitc.fit")["correct"]


def test_wave_returning_the_store_unchanged_is_caught(float32, monkeypatch):
    monkeypatch.setattr(online.PITCStore, "assimilate",
                        lambda self, X, y, runner=None: self)
    assert not go("sarcos-ppitc.waves")["correct"]


def test_wave_with_half_its_rows_left_out_is_caught(float32, monkeypatch):
    real = online.PITCStore.assimilate
    monkeypatch.setattr(
        online.PITCStore, "assimilate",
        lambda self, X, y, runner=None: real(self, X[:X.shape[0] // 2],
                                             y[:y.shape[0] // 2]))
    assert not go("sarcos-ppitc.waves")["correct"]


def test_wave_with_an_answer_altered_is_caught(float32, monkeypatch):
    real = online.PITCStore.assimilate

    def altered(self, X, y, runner=None):
        new = real(self, X, y, runner)
        store = new.store._replace(ydd=new.store.ydd.at[0].add(1.0))
        return dataclasses.replace(new, store=store)

    monkeypatch.setattr(online.PITCStore, "assimilate", altered)
    assert not go("sarcos-ppitc.waves")["correct"]


# -- the control ---------------------------------------------------------------------

@pytest.mark.parametrize("name", ["aimpeak-ppic.poisson", "sarcos-ppitc.fit"])
def test_control_reads_three_times_the_program(name, float32):
    """At |D| = 640, |S| = 128 the three-pass control's readings stand at
    least three times above the program's on one compared number, over two
    seeds: the rule by which the chip limits were set (lower reading: the
    program's largest; upper reading: the control's smallest)."""
    cell = small_cell(name, n=640, support=128)
    runs = [go(name, seed=s, cell=cell, control=True) for s in (1, 2)]
    assert all(r["correct"] for r in runs)
    ratios = []
    for key in runs[0]["checks"]:
        lower = max(r["checks"][key]["value"] for r in runs)
        upper = min(r["control"][key]["value"] for r in runs)
        ratios.append(upper / lower)
    assert max(ratios) >= 3.0, ratios


def test_control_matmul_is_three_bf16_passes(float32):
    from bench import reference
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64), jnp.float32)
    exact = reference.mm(a, a.T, "highest")
    low = reference.mm(a, a.T, reference.CONTROL)
    err = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    # three passes drop the lo*lo term: ~2^-16 relative, far above float32
    # round-off and far below one bf16 pass
    assert 1e-7 < err < 1e-3
