"""The check that decides ``correct``, driven through a whole run on the
CPU at the registry's smoke widths: the engine's tokens pass, the
control (the reference in float8) fails, and a token altered where the
engine produces it fails. (The chip's own runs at the cells' sizes set
the cells' limits; PERF.md gives those readings.)

At these widths the engine reads 0.004-0.008 and the control 0.09-0.19
(seeds 1 and 2**31+5, both configurations), so the small cells take the
limit 0.03."""
import pytest

import run as R
from conftest import small_cell

LIMIT = 0.03
SECONDS = 2.0


@pytest.mark.parametrize("config", ["smollm-135m", "mamba2-780m"])
def test_engine_tokens_pass(config):
    cell, cfg = small_cell(config, LIMIT)
    out = R.run_cell(cell, 2**31 + 5, SECONDS, False, cfg=cfg)
    assert out["correct"], out["check"]
    assert out["_info"]["judged_tokens"] > 100
    assert out["_info"]["window_compiles"] == 0
    assert list(out)[-2:] == ["check", "_info"]


@pytest.mark.parametrize("config", ["smollm-135m", "mamba2-780m"])
def test_control_fails(config):
    cell, cfg = small_cell(config, LIMIT)
    out = R.run_cell(cell, 1, SECONDS, False, cfg=cfg, control=True)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > LIMIT


def test_token_altered_where_produced_fails():
    cell, cfg = small_cell("smollm-135m", LIMIT)

    def alter(batcher):
        inner = batcher._decode

        def step(*args):
            toks, caches = inner(*args)
            return (toks.at[0].add(1) % cfg.vocab), caches

        batcher._decode = step

    out = R.run_cell(cell, 3, SECONDS, False, cfg=cfg, serve_hook=alter)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > LIMIT
