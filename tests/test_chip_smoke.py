"""CPU rehearsal of ``chip_smoke.py``: every phase function at a tiny size.

The script itself refuses to run without a TPU; these tests call its
phase functions directly (VGG11, a few images, requests and budgets) so
that a wrong path, argument or check fails here and not on the chip.
"""

import importlib.util
import pathlib
import shutil

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load(REPO / "chip_smoke.py")


def test_golden_phase(smoke):
    line = smoke.phase_golden("vgg11")
    assert "pallas_eq_numpy_bitwise" in line["checks"]
    assert "golden_density_atol_1e-2" in line["checks"]
    assert "golden_sample_sum_rtol_2e-2" in line["checks"]
    assert len(line["mean_cycles_rel_err_by_layer"]) == 8  # reported per layer


def test_capture_phase(smoke):
    line = smoke.phase_capture("vgg11", n_images=3, batch_images=2)
    assert line["checks"] == ["layer_count", "shapes", "rowbits_in_0_8P", "density_in_0_1"]


def test_fused_phase(smoke):
    line = smoke.phase_fused("vgg11", n_budgets=3, n_check=12, adc_bits=(2, 8))
    assert line["configs"] == 2 * 2 * 4 * 3
    assert line["oracle_max_rel_err"] <= 1e-12


def test_sharded_phase(smoke):
    line = smoke.phase_sharded("vgg11", n_budgets=2, adc_bits=(4,))
    assert "sharded_eq_one_device_elementwise" in line["checks"]


def test_replay_phase(smoke):
    line = smoke.phase_replay("vgg11", n_requests=12)
    assert "fabricsim_bit_identical_12req_x4" in line["checks"]


def test_fleet_phase(smoke):
    line = smoke.phase_fleet("vgg11", n_requests=24, n_prefix=6)
    assert "sketch_within_bound" in line["checks"]


def test_refuses_without_tpu(smoke, capsys):
    """On the CPU the script exits non-zero, names the missing TPU and
    prints no result line."""
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_refuses_outside_the_repository(tmp_path, capsys):
    """Alone in a directory, the script finds no program and exits non-zero
    before it imports jax."""
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", lone)
    assert _load(lone).main([]) == 2
    out, err = capsys.readouterr()
    assert "no repository" in err
    assert out == ""
