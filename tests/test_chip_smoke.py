"""``chip_smoke.py``'s phases and checks at a tiny size on the CPU.

The smoke's ``main()`` only runs on a TPU; its phase functions take their
sizes as arguments, so here they run through the same ``verify`` (numpy
reference + simulator parity + no fallbacks) with the kernels interpreted.
"""

import importlib.util
import pathlib

import pytest

from repro.engine.registry import get, hierarchy_spec
from repro.remote import MemoryHierarchy

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TINY = {
    "q3_phase": dict(lineitem_rows=4000, orders_rows=1000, customer_rows=100,
                     page_rows=64, budget=16, seed=0),
    "sort_phase": dict(n_keys=1 << 13, page_keys=128, budget=16, seed=0),
}


@pytest.mark.parametrize("phase", sorted(TINY))
def test_phase_matches_reference_and_simulator(smoke, phase):
    backend, seconds = smoke.verify(getattr(smoke, phase), **TINY[phase])
    assert seconds > 0.0
    assert backend.interpret is True  # CPU: the kernels are interpreted
    assert backend.wall.kernel_calls > 0
    assert backend.wall.kernel_fallbacks == 0


def test_checks_catch_a_wrong_output(smoke):
    """The reference and parity checks are not vacuous: one changed key in
    the sort's output fails both."""
    def run():
        target = MemoryHierarchy(hierarchy_spec(*smoke.TIERS))
        return smoke.sort_phase(target, **TINY["sort_phase"])

    good, bad = run(), run()
    smoke.check_reference(*good)
    session, result = bad
    tr = result.per_task[-1]
    page_id = get(tr.op).output_of(tr.result)[0]
    page = session.remote.peek_batch([page_id])[0]
    page[0] += 1  # peek returns the stored page itself
    with pytest.raises(RuntimeError, match="sort differs"):
        smoke.check_reference(*bad)
    with pytest.raises(RuntimeError, match="output pages differ"):
        smoke.check_parity(good, bad)


def test_main_refuses_without_a_tpu(smoke):
    with pytest.raises(SystemExit, match="'cpu'"):
        smoke.main()
