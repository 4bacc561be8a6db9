"""Tiny cells for the benchmark's own tests on the CPU.

``tiny_root`` lays out a benchmark root of its own: a BENCHMARK.json that
names cells the harness has never seen, and their configuration, traffic
and check files, beside the real drivers, metrics and references."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

TINY_CONFIGS = {
    "tiny-dense": {
        "num_hidden_layers": 2, "hidden_size": 256, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "vocab_size": 8192, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "attention_bias": False, "tie_word_embeddings": True,
        "dtype": "bfloat16", "kernel_impl": "pallas",
        "program": "smollm-360m", "reference": "dense_gqa",
        "flops": "dense_gqa"},
    "tiny-mamba2": {
        "n_layer": 2, "d_model": 256, "vocab_size": 8192,
        "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                    "expand": 2, "headdim": 32, "ngroups": 1,
                    "chunk_size": 32},
        "norm_epsilon": 1e-05, "tie_embeddings": True,
        "dtype": "bfloat16", "kernel_impl": "pallas",
        "program": "mamba2-1.3b", "reference": "mamba2", "flops": "mamba2",
        # no cell runs Mamba2 yet, so no configuration file maps its fields
        "program_fields": {
            "num_layers": "n_layer", "d_model": "d_model",
            "vocab_size": "vocab_size", "ssm_state_size": "ssm_cfg.d_state",
            "ssm_conv_width": "ssm_cfg.d_conv", "ssm_expand": "ssm_cfg.expand",
            "ssm_head_dim": "ssm_cfg.headdim",
            "ssm_chunk_size": "ssm_cfg.chunk_size", "norm_eps": "norm_epsilon",
            "tie_embeddings": "tie_embeddings", "dtype": "dtype",
            "kernel_impl": "kernel_impl"}},
}
TINY_TRAFFIC = {"driver": "generate", "batch": 4, "prompt_len": 64,
                "gen_len": 32, "capacity": 128, "check_requests": 4}
# At these sizes (CPU, seeds 1-3) the program read widest gap 0 to 0.005
# and logit error 0.004 to 0.012; the fp8 control 0.032 to 0.099 and
# 0.038 to 0.105: the limits lie between
TINY_LIMIT = 0.02
TINY_LOGIT_LIMIT = 0.025


def _smollm_fields() -> dict:
    return json.loads((BENCH / "configs" / "smollm-360m.json").read_text())[
        "program_fields"]


def make_tiny_root(tmp_path: Path) -> Path:
    for sub in ("drivers", "metrics", "reference", "flops"):
        shutil.copytree(BENCH / sub, tmp_path / sub)
    shutil.copy(BENCH / "peaks.json", tmp_path / "peaks.json")
    for sub in ("configs", "traffic", "checks"):
        (tmp_path / sub).mkdir()
    workloads = []
    for name, conf in TINY_CONFIGS.items():
        conf = dict({"program_fields": _smollm_fields()}, **conf, name=name)
        (tmp_path / "configs" / f"{name}.json").write_text(json.dumps(conf))
        cell = f"{name}.tiny-gen"
        workloads.append({"name": cell, "config": name, "traffic": "tiny-gen",
                          "chips": 1, "why": "test"})
        (tmp_path / "checks" / f"{cell}.json").write_text(json.dumps(
            {"widest_gap": {"limit": TINY_LIMIT},
             "logit_error": {"limit": TINY_LOGIT_LIMIT}}))
    (tmp_path / "traffic" / "tiny-gen.json").write_text(
        json.dumps(TINY_TRAFFIC))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"] = workloads
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
