"""``correct`` at toy sizes: sound runs pass, the bfloat16 control and
each fault of the timed path that a one-chip cell can have fail."""
import pytest

import reference as ref
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct_and_control_is_not(root, cell):
    res = tiny.drive(root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    limits = {name: lim for name, _, lim in res["checks"]}
    ok, _ = ref.verdict(res["control"], limits)
    assert not ok, res["control"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered",
                                   "unserved"])
def test_fault_is_caught(root, fault):
    res = tiny.drive(root, "tiny_qvga", fault=fault)
    assert not res["correct"], res["checks"]
