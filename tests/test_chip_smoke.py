"""chip_smoke.py on the CPU: its kernel, pipeline and serve phases at
``reduced()`` size (kernels interpreted), its refusal to run without a
TPU, the compile-cache placement its entry points share, and the depth cut
of the pipeline config."""
import dataclasses
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.pipeline import PipelineConfig  # noqa: E402


def _small_qwen3():
    """reduced() qwen3-1.7b, keeping GQA (two query heads per kv head)."""
    cfg = reduced(get_config("qwen3-1.7b"))
    return dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, n_kv_heads=2))


def test_kernel_phase_reduced():
    errs = chip_smoke.phase_kernels(_small_qwen3(),
                                    reduced(get_config("mamba2-780m")),
                                    seq=64, decode_batch=2, decode_cache=96)
    assert set(errs) == {"flash_attention", "flash_decode", "ssd"}


def test_pipeline_phase_reduced(tmp_path):
    cfg = PipelineConfig(
        arch="qwen3-1.7b", reduce=True, n_layers=3,
        platforms=("bf16", "bf16-chunk16"), selector="random",
        selector_args={"n_samples": 4, "seed": 0}, steps=12, seq_len=32,
        batch=2, workers=0, max_attempts=1)
    out = chip_smoke.phase_pipeline(cfg, str(tmp_path / "store"))
    assert out["intervals"] > 0
    assert set(out["replays"]) == {"bf16", "bf16-chunk16"}
    assert (tmp_path / "pipeline_manifest.json").exists()


def test_pipeline_phase_refuses_cache_hits(tmp_path, monkeypatch):
    """A cache hit would skip the device work: the phase fails on one, and
    so empties its store before every run."""
    cfg = PipelineConfig(
        arch="qwen3-1.7b", reduce=True, platforms=("f32",),
        selector="random", selector_args={"n_samples": 2, "seed": 0},
        steps=6, seq_len=16, batch=2, workers=0, max_attempts=1)
    store = str(tmp_path / "store")
    chip_smoke.phase_pipeline(cfg, store)
    with monkeypatch.context() as m:
        m.setattr(chip_smoke.shutil, "rmtree", lambda *a, **k: None)
        with pytest.raises(chip_smoke.SmokeFailure, match="cache hit"):
            chip_smoke.phase_pipeline(cfg, store)
    assert chip_smoke.phase_pipeline(cfg, store)["intervals"] > 0


def test_pipeline_builds_a_train_state_only_when_none_is_alive(
        tmp_path, monkeypatch):
    """At the chip's depth one train state takes most of the device: no
    stage may build a state while another one is still alive."""
    from repro.train import Trainer
    init = Trainer.init_state
    live, sizes = [], []

    def live_bytes():
        return sum(x.nbytes for x in jax.live_arrays())

    def counted(self):
        live.append(live_bytes())
        state = init(self)
        sizes.append(sum(x.nbytes for x in jax.tree.leaves(state)))
        return state

    cfg = PipelineConfig(
        arch="qwen3-1.7b", reduce=True, platforms=("f32", "f32-chunk16"),
        selector="random", selector_args={"n_samples": 3, "seed": 0},
        steps=8, seq_len=16, batch=2, workers=0, max_attempts=1)
    monkeypatch.setattr(Trainer, "init_state", counted)
    before = live_bytes()
    chip_smoke.phase_pipeline(cfg, str(tmp_path / "store"))
    assert len(live) > 2
    assert max(live) - before < min(sizes) / 2, (before, live, sizes)


def test_serve_phase_reduced():
    out = chip_smoke.phase_serve(reduced(get_config("qwen3-1.7b")), batch=2,
                                 max_seq=64, prefill_len=16, requests=3,
                                 mean_new=6, check_tokens=4)
    assert out["requests"] == 3
    assert out["logit_err"] < 1e-4          # f32 at reduced size


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main(["--out", os.devnull]) != 0
    cap = capsys.readouterr()
    assert "no TPU" in cap.err
    assert '"ok"' not in cap.out


def test_script_refuses_cpu_process():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_cache_placement(monkeypatch):
    from repro.launch.compile_cache import CHECKOUT, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache") == \
            os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_pipeline_depth_cut_keeps_published_widths():
    pub = get_config("qwen3-1.7b")
    cut = PipelineConfig(arch="qwen3-1.7b", reduce=False,
                         n_layers=6).base_cfg()
    assert cut == dataclasses.replace(pub, n_layers=6)
    assert PipelineConfig(arch="qwen3-1.7b", reduce=False).base_cfg() == pub
    assert chip_smoke.chip_pipeline_config().base_cfg() == \
        dataclasses.replace(pub, n_layers=chip_smoke.PIPELINE_LAYERS)
