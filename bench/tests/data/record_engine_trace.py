#!/usr/bin/env python3
"""Records ``engine_step.xplane.pb.gz`` and
``engine_step.op_names.json.gz``, the trace ``bench/tests/test_scopes.py``
reduces and its programs' instruction map: smollm-135m at the
registry's smoke widths on 4 slots (the small cell of
``bench/tests/conftest.py``), served through the CiM engine on one TPU
and traced from the window's opening fill to its first completion, with
the host's annotations alone.

    python3 bench/tests/data/record_engine_trace.py
"""
import gzip
import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parent)]

import conftest  # noqa: E402
import engine_trace  # noqa: E402
import run as R  # noqa: E402

SEED = 2**31 + 7
NAME = "engine_step"


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: needs a TPU chip", file=sys.stderr)
        return 3
    R.enable_compile_cache()
    cell, cfg = conftest.small_cell("smollm-135m", 1.0)
    keep = pathlib.Path(tempfile.mkdtemp())
    out = engine_trace.measure(cell, SEED, 0.05, trace=True, keep=str(keep),
                               whole=True, host_level=1, cfg=cfg)
    for suffix in ("xplane.pb", "op_names.json"):
        data = (keep / f"{cell.name}.{suffix}").read_bytes()
        (HERE / f"{NAME}.{suffix}.gz").write_bytes(gzip.compress(data, mtime=0))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
