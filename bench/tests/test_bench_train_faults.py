"""The fed-training check sees each fault the cell can have: a step that
returns its state unchanged, half of the batch left out, a token altered
where the batch is made."""
import pytest

import plants
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", plants.TRAIN)
def test_fault_makes_the_run_incorrect(root, fault, capsys):
    with plants.plant("train", fault):
        out = tiny.run(root, tiny.cells("train")[0], 3000000023, seconds=0.5,
                       capsys=capsys)
    assert out["correct"] is False, out["checks"]
