"""The comparison that decides `correct`, driven through the rest of a run
at a size the CPU holds: the program passes, and the bfloat16 control and
each fault planted under the timed path fail.

Each cell keeps its committed limits and its deployment with only the
neuron count cut; it starts from that small deployment grown from its empty
start, as its traffic's start state was grown at full size.
"""

import numpy as np
import pytest

import make_start
import run

CELLS = {"grow-131k": 512, "sweep-k8-16k": 256}
# The sweep is no cell of BENCHMARK.json (its search disagrees with the
# reference on the chip); its driver, deployment and limits stay, checked
# here at a size where program and reference agree.
SWEEP = dict(name="sweep-k8-16k", config="msp-sweep-k8-16k", traffic="growth", chips=1,
             why="the sweep's driver at a size where its search agrees with the reference")
GROWN = 1200  # a network this small forms its first synapses near step 700
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """Each cell's deployment at its small size, grown from the empty start."""
    out = tmp_path_factory.mktemp("states")
    for name in CELLS:
        cell = small(name, None)
        make_start.grow(cell["config"], name, [GROWN], 20230125, out)
    return out


def load_cell(name):
    """The cell as BENCHMARK.json gives it, the sweep added."""
    load_json = run.load_json

    def with_sweep(path):
        data = load_json(path)
        if path.name == "BENCHMARK.json":
            data["workloads"].append(SWEEP)
        return data

    run.load_json = with_sweep
    try:
        return run.load_cell(name)
    finally:
        run.load_json = load_json


def small(name, grown):
    cell = load_cell(name)
    cell["config"]["network"]["n"] = CELLS[name]
    if grown is not None:
        cell["start"] = grown / name / f"grown-{GROWN}.npz"
    return cell


def run_with(cell, fault=None, control=False):
    """One run, the driver's chunk program broken by `fault` if given."""
    build = run.load_module(run.HERE / "drivers" / f"{cell['config']['driver']}.py").build

    def broken_build(*args):
        driver = build(*args)
        chunk = driver.chunk
        driver.chunk = lambda state, key: fault(state, *chunk(state, key))
        return driver

    if fault is not None:
        original = run.load_module
        run.load_module = lambda path: (
            type("M", (), {"build": staticmethod(broken_build)})
            if path.parent.name == "drivers" else original(path))
        try:
            return run.run_cell(cell, SEED, 0.0, False, control=control)
        finally:
            run.load_module = original
    return run.run_cell(cell, SEED, 0.0, False, control=control)


def unchanged(state, out, recs):
    """The step returns its state unchanged (only the counter moves)."""
    return state._replace(step=out.step), recs


def half_left_out(state, out, recs):
    """Half of the batch is not stepped: the last half of the neurons of
    each replica keep their old state."""
    n = state.neurons.x.shape[-1]
    keep = np.arange(n) >= n // 2
    neurons = type(out.neurons)(*(np.where(keep, a, b) for a, b in
                                  zip(state.neurons, out.neurons)))
    return out._replace(neurons=neurons), recs


def synapse_altered(state, out, recs):
    """One synapse that the chunk kept, the first in slot order, ends at
    another dendrite (the warm-up chunk starts with none)."""
    kept = np.flatnonzero(np.asarray(out.edges.valid) & np.asarray(state.edges.valid))
    if not kept.size:
        return out, recs
    dst = np.asarray(out.edges.dst).copy()
    flat = dst.reshape(-1)
    flat[kept[0]] = (flat[kept[0]] + 1) % state.neurons.x.shape[-1]
    return out._replace(edges=out.edges._replace(dst=dst)), recs


def calcium_altered(state, out, recs):
    """One neuron's calcium is off by one part in a thousand."""
    ca = np.asarray(out.neurons.calcium).copy()
    ca.reshape(-1)[0] *= 1.001
    return out._replace(neurons=out.neurons._replace(calcium=ca)), recs


def partners_halved(monkeypatch):
    """The partner search returns no partner for the first half of the
    neurons: their requests are never sent."""
    from repro.core import traversal

    find = traversal.find_partners

    def halved(*args, **kwargs):
        partner = find(*args, **kwargs)
        return partner.at[: partner.shape[0] // 2].set(-1)

    monkeypatch.setattr(traversal, "find_partners", halved)


def update_skipped(monkeypatch):
    """The connectivity update returns the state it was given."""
    from repro.core.engine import PlasticityEngine

    monkeypatch.setattr(PlasticityEngine, "connectivity_update",
                        lambda self, state, *args, **kwargs: state)


def m2l_misplaced(monkeypatch):
    """The translated series of each box pair lands on the next candidate:
    a legal but wrong child wherever the Taylor tier decides."""
    import jax.numpy as jnp

    from repro.core import expansions

    series = expansions.box_mass_taylor_log
    monkeypatch.setattr(expansions, "box_mass_taylor_log",
                        lambda *a, **k: jnp.roll(series(*a, **k), 1, axis=-1))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_is_correct(name, grown):
    result = run_with(small(name, grown))
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(name, grown):
    result = run_with(small(name, grown), control=True)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", [unchanged, half_left_out, synapse_altered, calcium_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_fault_is_caught(name, fault, grown):
    result = run_with(small(name, grown), fault=fault)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("plant", [partners_halved, update_skipped], ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_update_fault_is_caught(name, plant, grown, monkeypatch):
    plant(monkeypatch)
    result = run_with(small(name, grown))
    assert not result["correct"], result["checks"]


def test_descent_fault_is_caught(grown, monkeypatch):
    """Only the network's search uses expansions at this size (the sweep's
    boxes of 8 x 256 neurons are too wide for its sigma of 300)."""
    m2l_misplaced(monkeypatch)
    result = run_with(small("grow-131k", grown))
    assert result["checks"]["descent_gap"]["value"] > result["checks"]["descent_gap"]["limit"]


def test_compile_inside_the_window_fails_the_run(grown):
    import jax
    import jax.numpy as jnp

    calls = []

    def compiles_late(state, out, recs):
        calls.append(1)
        if len(calls) > 1:  # the warm-up chunk is set-up; later chunks are the window
            jax.jit(lambda x: x * 2)(jnp.ones(3))
        return out, recs

    with pytest.raises(SystemExit, match="inside the window"):
        run_with(small("grow-131k", grown), fault=compiles_late)


def test_no_result_without_a_tpu(capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        run.main(["--workload", "grow-131k", "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""
