"""chip_smoke.py on the CPU: its phases at smoke geometry, and its refusal.

The script's real run is on a TPU at full width; here the same phase
functions run at smoke geometry (Pallas kernels interpreted) so the control
flow and the parity checks are exercised without a chip, and ``main()`` is
checked to fail, printing no result line, when JAX finds no TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SMALL_KERNELS = dict(sas_shape=(64, 256), patch=32, ffn=(64, 96, 128),
                     tokens=(2, 256, 16), pssa_shape=(1, 2, 256, 16))


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_main_fails_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compiled_policy_check():
    cpu_auto = chip_smoke.build_config("auto", smoke=True)
    with pytest.raises(chip_smoke.CheckFailed):      # reference on the CPU
        chip_smoke.check_compiled_policy(
            cpu_auto.unet.effective_kernel_policy().describe())
    fused = chip_smoke.build_config("fused", smoke=True)
    described = fused.unet.effective_kernel_policy().describe()
    with pytest.raises(chip_smoke.CheckFailed):      # interpreted here
        chip_smoke.check_compiled_policy(described)
    chip_smoke.check_compiled_policy({**described,
                                      "interpret_resolved": False})


def test_kernel_exactness_phase_interpreted():
    out = chip_smoke.kernel_exactness_phase(interpret=True, **SMALL_KERNELS)
    assert out == {"bitmap_bit_identical": True, "dbsc_bit_identical": True,
                   "reuse_delta_bit_identical": True,
                   "pssa_counters_bit_identical": True}


def test_one_chip_phases_at_smoke_geometry(capsys):
    cfg = chip_smoke.build_config("fused", smoke=True, steps=2)
    cfg_ref = chip_smoke.build_config("reference", smoke=True, steps=2)
    chip_smoke.run_one_chip(cfg, cfg_ref, interpret=True, **SMALL_KERNELS)
    out = capsys.readouterr().out
    for name in ("nnz", "ones_xor", "important"):
        assert f"counter {name}: equal=" in out
    assert "served 4 requests" in out


def test_parity_phase_reports_counter_differences():
    a = {"nnz": [[1.0, 2.0]], "ones_xor": [[3.0, 4.0]],
         "important": [[[True, False]], [[True, True]]]}
    b = {"nnz": [[1.0, 2.0]], "ones_xor": [[3.0, 5.0]],
         "important": [[[True, False]], [[True, True]]]}
    got = chip_smoke.compare_counters(a, b)
    assert got["nnz"]["equal"] and got["important"]["equal"]
    assert not got["ones_xor"]["equal"]
    assert got["ones_xor"]["entries_differing"] == 1


def test_four_chip_phase_on_fake_devices():
    """--four-chips' phase on four simulated host devices (own process).

    The fused route runs its Pallas kernels per data shard under the mesh
    (``kernels.runtime.data_parallel``), as it must on the chip.
    """
    script = (
        "from repro.launch.mesh import simulate_host_devices\n"
        "simulate_host_devices(4)\n"
        "import chip_smoke\n"
        "cfg = chip_smoke.build_config('fused', smoke=True, steps=2)\n"
        "chip_smoke.run_four_chips(cfg)\n"
        "print('FOUR_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR_OK" in r.stdout, r.stdout + r.stderr
    line = next(x for x in r.stdout.splitlines() if "dp=4 vs dp=1" in x)
    rec = json.loads(line.split("dp=4 vs dp=1: ", 1)[1])
    assert rec["devices_holding_batch"] == 4 and rec["batch_sharded"]
    assert rec["ledger_bit_identical"] and rec["ledger_max_rel_diff"] == 0.0
    assert rec["planted_fault_ledger_max_rel_diff"]["dropped"] \
        > chip_smoke.LEDGER_REL_TOL
