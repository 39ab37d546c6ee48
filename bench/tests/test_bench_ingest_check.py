"""The ingest cells' check at a size the CPU can run: sound runs pass, and
the control (RS(10, 3) parity where the configuration states RS(10, 4))
fails."""
import pytest

import plants
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", tiny.cells("ingest"))
def test_sound_run_is_correct(root, cell, capsys):
    out = tiny.run(root, cell, 3000000011, capsys=capsys)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_control_fails(root, capsys):
    with plants.plant("ingest", "control"):
        out = tiny.run(root, tiny.cells("ingest")[0], 3000000012, capsys=capsys)
    assert out["correct"] is False
    assert out["checks"]["parity_bytes_differing"]["value"] > 0
