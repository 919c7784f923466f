"""``chip_smoke.py`` rehearsed on the CPU mesh: the same phases, in the
same process, at the tiny sizes — each phase line parses and passed, the
exit status is non-zero because there is no chip, and the final line (the
one the driver reads on the chip) has exactly the keys ``ok`` and
``device``."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


@pytest.fixture
def smoke_out(smoke, tmp_path, monkeypatch):
    """Records go under tmp_path, not into the checkout."""
    monkeypatch.setattr(smoke, "OUT_DIR", tmp_path / "chip_smoke")
    return smoke


def _lines(capsys) -> list[dict]:
    out = capsys.readouterr().out
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


# the one-chip phases in two cases, the second in
# tests/test_chip_smoke_hybrids.py: all eleven in one case would be (ten were) the suite's
# longest (227 s of a worker under ``--dist loadfile``)
MAIN_PATHS = ("kernels", "train", "proxy", "serve", "moe")
HYBRIDS = ("hybrid", "latent_moe", "linear_moe", "conv_moe", "swa_moe",
           "headgate_moe", "sparse_linear_ops")


def check_tiny_phases(smoke_out, capsys, argv, phases):
    rc = smoke_out.main(["--tiny", *argv])
    lines = _lines(capsys)
    assert rc != 0, "there is no chip here: the smoke must not pass"
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert lines[0]["phase"] == "start"
    assert lines[0]["device"]["platform"] == "cpu"
    for name in phases:
        line = by_phase[name]
        assert line["ok"] is True, line.get("error")
        assert {"seconds", "shapes", "checks"} <= set(line)
    assert set(by_phase) == {"start", "end", *phases}
    assert by_phase["end"]["ok"] is True
    # no result line: nothing printed says a TPU ran this
    assert all("phase" in ln for ln in lines)
    assert '"platform": "tpu"' not in json.dumps(lines)


@pytest.mark.parametrize("argv,phases", [
    (["--phases", ",".join(MAIN_PATHS)], MAIN_PATHS),
    (["--chips", "4"], ("mesh_proxies", "spmd", "kv_shard")),
], ids=["one_chip", "four_chips"])
def test_phases_pass_at_tiny_size_and_exit_nonzero_off_tpu(
        smoke_out, capsys, eight_devices, argv, phases):
    check_tiny_phases(smoke_out, capsys, argv, phases)
    # the two one-chip cases leave no phase out
    assert tuple(n for n, _ in smoke_out.ONE_CHIP) == MAIN_PATHS + HYBRIDS


def test_full_size_run_refuses_to_start_off_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_final_line_has_exactly_ok_and_device(smoke, capsys, monkeypatch):
    """What the driver reads on the chip: with every phase passing on a
    TPU the last line is ``{"ok": true, "device": {...}}`` and nothing
    more in it."""
    import jax

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(smoke, "ONE_CHIP",
                        (("nothing", lambda sz: {"checks": "none"}),))
    monkeypatch.setattr(smoke, "memory_now", dict)
    monkeypatch.setattr(
        "dlnetbench_tpu.core.executor.enable_persistent_cache",
        lambda: "unused")
    assert smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    # and a failing phase withholds it
    def boom(sz):
        raise smoke.SmokeFailure("synthetic")
    monkeypatch.setattr(smoke, "ONE_CHIP", (("boom", boom),))
    assert smoke.main([]) != 0
    lines = _lines(capsys)
    assert lines[-1]["phase"] == "end" and lines[-1]["ok"] is False
    assert lines[-2]["error"].startswith("SmokeFailure: synthetic")
