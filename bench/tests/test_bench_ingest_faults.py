"""The ingest check sees each fault the cell can have: a step that leaves
the store unchanged, half of each batch left out, a token altered where it
is packed."""
import pytest

import plants
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "token_altered"])
def test_fault_makes_the_run_incorrect(root, fault, capsys):
    with plants.plant("ingest", fault):
        out = tiny.run(root, tiny.cells("ingest")[0], 3000000013, capsys=capsys)
    assert out["correct"] is False, out["checks"]
