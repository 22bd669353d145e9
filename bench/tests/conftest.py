"""Tests of the benchmark. They run on the CPU (``JAX_PLATFORMS=cpu``)
and never load the TPU's library:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# the smoke widths of the program's registry, written in each
# configuration's own keys
SMALL = {
    "smollm-135m": dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                        num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                        vocab_size=256),
    "mamba2-780m": dict(d_model=64, n_layer=2, vocab_size=256, d_state=16,
                        headdim=16, chunk_size=8),
}
# a closed loop small enough for the Pallas interpreter
SMALL_MIX = {"loop": "closed", "backlog": 2, "block": 4,
             "prompt_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 3, "max": 12},
             "max_new": {"dist": "uniform", "min": 4, "max": 12}}


def small_cell(config: str, limit: float):
    """A cell of ``config`` at the registry's smoke widths, with its
    program configuration, for runs on the CPU."""
    import run as R
    from repro.models.registry import get_config

    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    c.update(SMALL[config])
    e2e = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    cell = R.Cell(name="small", entry={"chips": 1}, config=c,
                  model=R.load_module(BENCH / "configs" / f"{config}.py"),
                  mix=SMALL_MIX,
                  settings={"n_slots": 4, "s_max": 64, "check": {"max_gap": limit}},
                  end_to_end=e2e, per_layer=[])
    return cell, get_config(config, smoke=True)


@pytest.fixture
def bench_json():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
