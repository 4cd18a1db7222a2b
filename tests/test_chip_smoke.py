"""chip_smoke.py's output contract, pinned where no chip is needed.

The driver reads ONE thing: the last line of stdout, a JSON object with
exactly ``ok`` and ``device`` (``platform``, ``kind``, ``count``).  These
tests hold that line in every way the script can end — pass, fail, a phase
that raises, the four-chip option — and hold the rehearsal property: on the
CPU every phase walks its whole control flow at the script's own tiny size
and fails ONLY the checks that need the chip.
"""
import io
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
SUBPROCESS_TIMEOUT = 420    # a cold CPU run is ~30 s; generous on a busy host

DEVICE_KEYS = {"platform", "kind", "count"}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# what a CPU rehearsal may fail: exactly the checks that need the chip
CHIP_ONLY_CHECKS = {
    "device": {"platform_is_tpu", "memory_stats_present"},
    "train": set(),
    "train_long": {"flash_kernel_in_compiled_step"},
    "serve": {"paged_kernel_dispatched", "paged_kernel_in_programs"},
    # XLA:CPU copies the pool around the interpreted kernel; the v5e's
    # compiler is the one asked
    "serve_pool": {"paged_kernel_in_programs", "no_pool_sized_moves"},
    "dp4": {"flash_kernel_in_compiled_step"},
}


def _assert_contract(line: str, *, ok: bool, platform: str, count=None):
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"}, obj
    assert set(obj["device"]) == DEVICE_KEYS, obj
    assert obj["ok"] is ok
    assert obj["device"]["platform"] == platform
    assert isinstance(obj["device"]["kind"], str)
    assert isinstance(obj["device"]["count"], int)
    if count is not None:
        assert obj["device"]["count"] == count
    return obj


@pytest.mark.parametrize("ok,device", [(True, TPU), (False, TPU),
                                       (False, CPU)])
def test_final_line_has_exactly_the_contract_keys(ok, device):
    # extra keys on the input must not leak into the line
    line = chip_smoke.final_line(ok, dict(device, seconds=1.0, error="x"))
    assert "\n" not in line
    _assert_contract(line, ok=ok, platform=device["platform"],
                     count=device["count"])


def _run_main(monkeypatch, phases, device, argv=()):
    """main() in-process with the phase table and the device swapped."""
    monkeypatch.setattr(chip_smoke, "PHASES", phases)
    monkeypatch.setattr(chip_smoke, "describe_device", lambda: dict(device))
    # not in the test process: the cache setting is process-global
    monkeypatch.setattr("distributed_tensorflow_tpu.utils."
                        "enable_compile_cache", lambda: "<not enabled>")
    out = io.StringIO()
    code = chip_smoke.main(list(argv), out)
    lines = out.getvalue().splitlines()
    return code, lines


def _passing(_ctx):
    return {"checks": {"fine": True}}


def _raising(_ctx):
    raise RuntimeError("first line\nthe last line of the exception")


ALL_PASS = {name: _passing for name in chip_smoke.PHASES}


def test_all_phases_pass_on_tpu_is_ok_and_exit_0(monkeypatch, tmp_path):
    code, lines = _run_main(monkeypatch, ALL_PASS, TPU,
                            [f"--out={tmp_path}"])
    assert code == 0
    _assert_contract(lines[-1], ok=True, platform="tpu", count=1)
    assert [json.loads(l)["phase"] for l in lines[:-1]] \
        == list(chip_smoke.ONE_CHIP)


def test_cpu_is_never_a_pass_even_with_every_phase_ok(monkeypatch, tmp_path):
    code, lines = _run_main(monkeypatch, ALL_PASS, CPU,
                            [f"--out={tmp_path}"])
    assert code != 0
    _assert_contract(lines[-1], ok=False, platform="cpu")


def test_raising_phase_still_ends_with_the_contract_line(monkeypatch,
                                                         tmp_path):
    phases = dict(ALL_PASS, train=_raising)
    code, lines = _run_main(monkeypatch, phases, TPU, [f"--out={tmp_path}"])
    assert code != 0
    _assert_contract(lines[-1], ok=False, platform="tpu")
    by_phase = {json.loads(l)["phase"]: json.loads(l) for l in lines[:-1]}
    assert by_phase["train"]["ok"] is False
    assert by_phase["train"]["error"].endswith(
        "the last line of the exception")
    # the phases after it still ran and reported
    assert by_phase["serve"]["ok"] is True


def test_failed_check_fails_the_run(monkeypatch, tmp_path):
    phases = dict(ALL_PASS, serve=lambda _ctx: {
        "checks": {"fine": True, "paged_kernel_dispatched": False}})
    code, lines = _run_main(monkeypatch, phases, TPU, [f"--out={tmp_path}"])
    assert code != 0
    _assert_contract(lines[-1], ok=False, platform="tpu")


def _run_script(tmp_path, *argv, devices: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, SCRIPT, f"--out={tmp_path / 'out'}", *argv],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=SUBPROCESS_TIMEOUT)
    lines = proc.stdout.splitlines()
    assert lines, proc.stderr[-2000:]
    return proc, lines


def _assert_only_chip_checks_failed(phase_lines):
    for line in phase_lines:
        assert "error" not in line, line
        failed = {k for k, v in line["checks"].items() if not v}
        assert failed <= CHIP_ONLY_CHECKS[line["phase"]], (line["phase"],
                                                            failed)
        assert line["ok"] is (not failed)


def test_cpu_run_walks_every_phase_and_fails_truthfully(tmp_path):
    proc, lines = _run_script(tmp_path, devices=1)
    assert proc.returncode != 0
    assert proc.stdout.endswith(lines[-1] + "\n")     # nothing after it
    _assert_contract(lines[-1], ok=False, platform="cpu", count=1)
    phase_lines = [json.loads(l) for l in lines[:-1]]
    assert [l["phase"] for l in phase_lines] == list(chip_smoke.ONE_CHIP)
    _assert_only_chip_checks_failed(phase_lines)
    for line in phase_lines:
        assert {"seconds", "compile_seconds", "run_seconds",
                "compiles"} <= set(line)
    # what the library prints (TrainSession's "Restored checkpoint ...")
    # went to stderr, not between the JSON lines
    assert "Restored checkpoint" in proc.stderr
    # the cache went where the environment said, and nowhere else
    assert os.listdir(tmp_path / "jax_cache")
    assert not os.path.exists(tmp_path / ".jax_cache")


def test_chips_4_runs_only_the_four_chip_phase(tmp_path):
    proc, lines = _run_script(tmp_path, "--chips=4", devices=4)
    assert proc.returncode != 0
    _assert_contract(lines[-1], ok=False, platform="cpu", count=4)
    phase_lines = [json.loads(l) for l in lines[:-1]]
    assert [l["phase"] for l in phase_lines] == list(chip_smoke.FOUR_CHIPS)
    _assert_only_chip_checks_failed(phase_lines)
    dp4 = phase_lines[0]
    assert dp4["param_devices"] == dp4["batch_devices"] == 4
