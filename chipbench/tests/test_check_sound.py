"""The check passes a sound run and fails the control put in the
program's place, through the harness's own judgement."""
from chipbench.tests import tiny


def test_sound_run_is_correct_and_control_fails(tmp_path):
    res = tiny.run_in_child(tmp_path, seed=3_000_000_007, extra={"control": True})
    assert res["program_correct"] is True, res["program_numbers"]
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert res["correct"] is False, res["checks"]
    limits = tiny.CONFIG["check"]["limits"]
    assert any(c["name"] in limits and not c["ok"] for c in res["checks"])
