"""chip_smoke.py rehearsed on the CPU (ISSUE 21): the same phases the chip
runs, at tiny size with the Pallas kernels interpreted, so the command is
debugged here before chip time is spent — plus the contract that the
script itself refuses to pass without a chip, and the compile-cache
helper every entry point shares."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# the wave grower + Pallas kernels are the TPU routing; on the CPU they
# must be asked for (and run interpreted)
TINY = {"num_leaves": 15, "min_data_in_leaf": 5, "tree_grow_mode": "wave",
        "tpu_histogram_impl": "pallas"}


@pytest.fixture(scope="module")
def tiny_data():
    X, y, Xh, yh = chip_smoke.make_data(4096, 2048)
    return chip_smoke.bin_data(X, y), (Xh, yh)


@pytest.mark.parametrize("pipeline", [None, "dma"])
def test_kernel_selfcheck_interpreted(pipeline):
    out = chip_smoke.kernel_selfcheck(4096, features=4, interpret=True,
                                      pipeline=pipeline)
    assert out["leaves_q8_b256_abs"] == 0
    assert out["leaves_q8_b16_packed4_abs"] == 0
    assert out["row_update_mismatches"] == 0
    assert out["row_update_fetch_mismatches"] == 0
    assert out["trial_channels_fetch_mismatches"] == 0
    assert {k for k in out if k.endswith("_rel")} == {
        "single_b256_rel", "leaves_b256_rel", "single_b16_packed4_rel",
        "leaves_b16_packed4_rel", "single_refit_rel"}


@pytest.mark.parametrize("quantized", [True, False])
def test_train_phase_rehearsal(tiny_data, quantized):
    params = {**chip_smoke.BASE_PARAMS, **TINY,
              **(chip_smoke.Q8_PARAMS if quantized else {})}
    facts = chip_smoke.train_phase(
        "q8" if quantized else "exact", *tiny_data, params, 2,
        quantized=quantized, auc_floor=0.6, on_tpu=False)
    assert facts["hist_passes_last"] > 0
    want = "leaves_q8" if quantized else "leaves"
    assert any(f"/{want}/" in site for site in facts["kernels"])


def test_train_phase_fails_on_the_wrong_grower(tiny_data):
    """The phase asserts what ran: a config that silently lands on the
    partitioned grower (the old off-TPU downgrade) fails it."""
    params = {**chip_smoke.BASE_PARAMS, "num_leaves": 15}
    with pytest.raises(RuntimeError, match="grower is"):
        chip_smoke.train_phase("exact", *tiny_data, params, 2,
                               quantized=False, on_tpu=False)


def test_data_parallel_phase_rehearsal(tiny_data):
    facts = chip_smoke.data_parallel_phase(*tiny_data, steps=1, extra=TINY,
                                           auc_floor=0.6, on_tpu=False)
    assert facts["max_abs_diff"] <= chip_smoke.DP_TOL
    assert len(jax.devices()) == 8      # conftest's virtual mesh


def test_script_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    last = proc.stdout.strip().splitlines()[-1]
    assert not last.startswith("{"), "printed a result without a chip"
    assert "platform=cpu" in last


# -- the compile-cache helper ------------------------------------------------

def test_cache_helper_leaves_jax_alone_when_env_is_set(monkeypatch,
                                                       tmp_path):
    from lightgbm_tpu.utils import cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.configure_compile_cache() == str(tmp_path)
    assert cache.cache_root() == str(tmp_path)
    assert calls == []


def test_cache_helper_defaults_to_the_checkout(monkeypatch):
    from lightgbm_tpu.utils import cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert cache.configure_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    # autotune winners and native builds live under the same root
    from lightgbm_tpu.learner import autotune
    from lightgbm_tpu.utils import native
    monkeypatch.delenv("LGBM_TPU_AUTOTUNE_CACHE", raising=False)
    assert autotune._cache_path() == os.path.join(want,
                                                  "hist_autotune.json")
    assert native._build_dir() == os.path.join(want, "native")


def test_cache_paths_are_not_built_from_moving_parts():
    from lightgbm_tpu.learner import autotune
    from lightgbm_tpu.utils import cache
    src = open(cache.__file__).read()
    for word in ("tempfile", "getpid", "time", "expanduser", "HOME"):
        assert word not in src, word
    src = open(autotune.__file__).read()
    for word in ("tempfile", "getpid", "expanduser", "HOME"):
        assert word not in src, word


def test_last_line_contract(monkeypatch, capsys):
    """With a chip reported, main() ends on the one JSON object the
    driver parses; phases are stubbed — this checks the envelope only."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "find_device", lambda: dev)
    monkeypatch.setattr(chip_smoke, "kernel_selfcheck", lambda n: {})
    monkeypatch.setattr(chip_smoke, "make_data", lambda r, h: (0, 0, 0, 0))
    monkeypatch.setattr(chip_smoke, "bin_data", lambda X, y: None)
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a, **k: {})
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    assert chip_smoke.main(rows=1, holdout=1, steps=1) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
