"""A four-chip cell on four host CPU devices: the pPIC fit through
``ShardMapRunner`` with one block per device, checked against the routed
pPIC reference, and each planted fault caught; a configuration whose blocks
do not match the chips refused at once."""
import dataclasses
import json

import pytest

from bench import run
from bench.deploy import Deployment
from bench.tests.test_bench_cells import SMALL, go, on_devices, small_cell

NAME = "aimpeak-ppic.fit-4chip"
CHIPS = 4
BLOCK_FIELDS = ("Xb", "yb", "V", "C_L", "Wy", "centroids")


def config(name: str) -> dict:
    return json.loads((run.BENCH / "configs" / f"{name}.json").read_text())


def ppic_fit_cell() -> run.Cell:
    """The fit traffic, its limits and metrics, over the routed pPIC
    configuration at the tests' small size with one block per chip."""
    cell = small_cell("sarcos-ppitc.fit")
    cfg = dict(config("aimpeak-ppic"), **{**SMALL, "machines": CHIPS})
    cfg["serve"] = dict(cfg["serve"], max_batch=8)
    return dataclasses.replace(cell, name=NAME, chips=CHIPS, config=cfg)


def one_block_per_device(a) -> bool:
    shards = a.addressable_shards
    return (len(shards) == CHIPS
            and len({s.device for s in shards}) == CHIPS
            and all(s.data.shape[0] == 1 for s in shards))


def _no_exchange():
    """The exchange between blocks left out: block 0's factor alone."""
    from repro.core import online
    real = online._whitened_chol
    online._whitened_chol = lambda G, alive=None: real(G[:1])


def _half_data():
    """Half of the data left out, still one block per chip."""
    from repro.core import api
    method = api.get("ppic")

    def half(kfn, params, X, y, *, S, runner):
        h = X.shape[0] // 2
        return method.fit(kfn, params, X[:h], y[:h], S=S, runner=runner)

    api.REGISTRY["ppic"] = dataclasses.replace(method, fit=half)


FAULTS = {"no_exchange": _no_exchange, "half_data": _half_data}


def four_chip_run(fault: str | None = None) -> dict:
    """Run in a process with four devices: one run of the cell, with
    ``fault`` planted, and the placement of every state it fitted."""
    from bench import deploy

    placed = []
    real_fit = deploy.Deployment.fit

    def fit(self, X, y, S):
        model = real_fit(self, X, y, S)
        placed.append({f: one_block_per_device(getattr(model.state, f))
                       for f in BLOCK_FIELDS})
        return model

    deploy.Deployment.fit = fit
    if fault:
        FAULTS[fault]()
    return {"result": go(NAME, cell=ppic_fit_cell()), "placed": placed}


def run_on_four(fault: str | None = None) -> dict:
    return on_devices(CHIPS, "import json\n"
                      "from bench.tests.test_four_chips import four_chip_run\n"
                      f"print(json.dumps(four_chip_run({fault!r})))")


@pytest.fixture(scope="module")
def sound():
    return run_on_four()


def test_four_chip_ppic_fit_agrees_with_the_reference(sound):
    r = sound["result"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == CHIPS


def test_four_chip_fit_state_lies_one_block_per_device(sound):
    assert sound["placed"]
    for fit in sound["placed"]:
        assert all(fit.values()), fit


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_four_chip_fit_fault_is_caught(fault):
    r = run_on_four(fault)["result"]
    assert not r["correct"], r["checks"]


def test_blocks_that_do_not_match_the_chips_are_refused():
    cfg = config("aimpeak-ppic")
    assert cfg["machines"] == 20
    with pytest.raises(ValueError, match="needs machines = 4"):
        Deployment(cfg, chips=CHIPS)
