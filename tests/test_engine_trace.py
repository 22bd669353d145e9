"""The serving engine's own spans, counters and named scopes.

``ContinuousBatcher.step()`` marks its host work with
``repro.profile.trace.span`` (a ``jax.profiler.TraceAnnotation``), counts
what its fills and decode steps computed and how much of it served a
request, and the fused programs name their parts with
``jax.named_scope`` (metadata only: the optimized HLO is the same with
and without them).
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.profile.trace import SCOPES, hlo_op_names, scope_of, span
from repro.serve.engine import ContinuousBatcher, Request

NEW_COUNTERS = ("fill_rows_new", "fill_rows_computed", "fill_tokens_prompt",
                "fill_tokens_computed", "decode_rows_active", "decode_rows_computed")


def _engine(arch="smollm-135m", quant=None, n_slots=2, s_max=32):
    cfg = get_config(arch, smoke=True)
    if quant is not None:
        cfg = cfg.replace(quant=quant)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return ContinuousBatcher(params, cfg, n_slots=n_slots, s_max=s_max)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def _serve_spans(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


FILL = ["serve.fill.stage", "serve.fill.dispatch", "serve.fill.fetch",
        "serve.fill.commit"]
DECODE = ["serve.decode.stage", "serve.decode.dispatch", "serve.decode.fetch",
          "serve.decode.commit"]


def test_fill_and_decode_steps_emit_their_spans_in_order(tmp_path):
    b = _engine(quant=L.QuantConfig(mode="off"))
    b.submit(Request(100, [4, 4], max_new=2))
    b.run()                                    # compile outside the trace
    b.submit(Request(7, [5, 6], max_new=3))
    b.submit(Request(9, [1, 2, 3], max_new=3))
    jax.profiler.start_trace(str(tmp_path))
    b.step()                                   # a fill, then a decode step
    b.step()                                   # a decode step alone
    jax.profiler.stop_trace()
    spans = _serve_spans(tmp_path)
    assert [s[0] for s in spans] == (["serve.step"] + FILL + DECODE
                                     + ["serve.step"] + DECODE)
    steps = [i for i, s in enumerate(spans) if s[0] == "serve.step"]
    for lo, hi in zip(steps, steps[1:] + [len(spans)]):
        _, t0, t1, _ = spans[lo]
        children = spans[lo + 1:hi]
        assert all(t0 <= c[1] and c[2] <= t1 for c in children)
        # children follow each other without overlapping
        assert all(a[2] <= b_[1] for a, b_ in zip(children, children[1:]))
    stage = spans[1][3]
    assert stage["rows"] == 2 and stage["s_pad"] == 4
    assert stage["rids"] == "[7, 9]"
    assert spans[5][3] == {"active": 2} and spans[10][3] == {"active": 2}


def test_span_is_a_trace_annotation():
    assert isinstance(span("serve.x", rows=1), jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_counters_match_a_scripted_sequence():
    b = _engine(quant=L.QuantConfig(mode="off"))
    reqs = [Request(0, [1, 2, 3], max_new=3), Request(1, [1, 2, 3, 4, 5], max_new=2),
            Request(2, [7, 8], max_new=4)]
    for r in reqs:
        b.submit(r)
    b.step()
    # fill of r0 and r1 at bucket 8 (longest prompt 5) over both slots,
    # then one decode step over both; r1 finishes
    assert {k: b.stats()[k] for k in NEW_COUNTERS} == {
        "fill_rows_new": 2, "fill_rows_computed": 2, "fill_tokens_prompt": 8,
        "fill_tokens_computed": 16, "decode_rows_active": 2,
        "decode_rows_computed": 2}
    b.run()
    # r2 fills slot 1 alone at bucket 4; decode steps: {r0, r2}, {r2}, {r2}
    assert b.stats() == {
        "decode_steps": 4, "host_syncs": 6, "prefill_batches": 2,
        "fill_rows_new": 3, "fill_rows_computed": 4, "fill_tokens_prompt": 10,
        "fill_tokens_computed": 24, "decode_rows_active": 6,
        "decode_rows_computed": 8}
    assert [len(r.generated) for r in reqs] == [3, 2, 4]


def test_existing_stats_keys_unchanged():
    b = _engine(quant=L.QuantConfig(mode="off"))
    b.submit(Request(0, [3, 1], max_new=2))
    b.run()
    s = b.stats()
    assert list(s)[:3] == ["decode_steps", "host_syncs", "prefill_batches"]
    assert set(s) == {"decode_steps", "host_syncs", "prefill_batches", *NEW_COUNTERS}


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------


def _programs(b, s_pad=8):
    """Optimized HLO text of the engine's fused decode and fill programs."""
    n = b.n_slots
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    decode = b._decode.lower(b.params, i32(n, 1), b.caches, i32(n), i32(n),
                             key).compile().as_text()
    fill = b._prefill.lower(b.params, b.caches, i32(n, s_pad), i32(n),
                            jnp.zeros((n,), bool), key).compile().as_text()
    return decode, fill


def _canonical(text):
    """The HLO text less each instruction's metadata and the table of
    source locations it points into, with instruction and computation
    names replaced by their order of first appearance (XLA's name
    uniquifier numbers some fused instructions by the order the lowering
    created them in)."""
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text, flags=re.S)
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-780m"])
def test_scopes_are_metadata_only(arch, monkeypatch):
    """Both fused programs compile to the same optimized HLO with and
    without the named scopes, metadata and instruction names aside."""
    scoped = _programs(_engine(arch))
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        plain = _programs(_engine(arch))
    jax.clear_caches()
    assert any("/cim/" in v for v in hlo_op_names(scoped[0]).values())
    assert not any("/cim/" in v for v in hlo_op_names(plain[0]).values())
    for a, b in zip(scoped, plain):
        assert _canonical(a) == _canonical(b)


def _probe(monkeypatch, module, name, tag):
    """Wrap ``module.name`` so each call's ops carry ``tag<i>`` on their
    op_name path, inside whatever scope the caller opened."""
    real = getattr(module, name)
    calls = []

    def probe(*args, **kwargs):
        calls.append(len(calls))
        with jax.named_scope(f"{tag}{calls[-1]}"):
            return real(*args, **kwargs)

    monkeypatch.setattr(module, name, probe)
    return calls


def _tagged(op_names, tag, i):
    pat = re.compile(rf"(^|/){tag}{i}(/|$)")
    return [v for v in op_names.values() if pat.search(v)]


def test_every_cim_call_and_attention_op_is_scoped(monkeypatch):
    """In the smoke decode program every CiM MAC (the execution shim
    call inside dense()) is in a ``cim`` scope, and every op of the
    attention core and of the cache reads and writes is in ``attn``, and
    the KV write after the layer scan in ``kv.write``."""
    macs = _probe(monkeypatch, L, "exec_mac", "mac_probe")
    sdpa = _probe(monkeypatch, attn_lib, "_sdpa", "sdpa_probe")
    rows = _probe(monkeypatch, attn_lib, "write_cache_rows", "rows_probe")
    jax.clear_caches()
    b = _engine()                                 # registry default: mode cim
    decode, fill = _programs(b)
    names = hlo_op_names(decode)
    # 7 projections a layer, traced once in the layer scan's body of
    # each program
    assert len(macs) == 2 * 7
    assert len(sdpa) >= 1 and len(rows) >= 2
    for calls, tag, want in ((macs, "mac_probe", "cim"), (sdpa, "sdpa_probe", "attn"),
                             (rows, "rows_probe", "attn")):
        for i in calls:
            ops = _tagged(names, tag, i) or _tagged(hlo_op_names(fill), tag, i)
            assert ops, (tag, i)
            assert {scope_of(v) for v in ops} == {want}, (tag, i)
    # the cache writes after the layer scan, the unembedding and sampling
    scopes = {scope_of(v) for v in names.values()}
    assert {"attn", "kv.write", "cim", "unembed", "sample"} <= scopes
    assert "fill.merge" in {scope_of(v) for v in hlo_op_names(fill).values()}
    monkeypatch.undo()
    jax.clear_caches()


def test_ssm_scope_covers_the_recurrence():
    """The state update and the C·h read-out of mamba2's recurrent step
    are in the ``ssm`` scope, and every op on a path through it too
    (its projections in ``cim``)."""
    b = _engine("mamba2-780m")
    decode, _ = _programs(b)
    names = hlo_op_names(decode).values()
    for einsum in ("bh,bhn,bhp->bhpn", "bhn,bhpn->bhp"):
        ops = [v for v in names if einsum in v]
        assert ops and {scope_of(v) for v in ops} == {"ssm"}, einsum
    assert {scope_of(v) for v in names if "/ssm/" in v} == {"ssm", "cim"}
    assert {"ssm", "cim", "unembed", "sample"} <= {scope_of(v) for v in names}


def test_scope_of_takes_the_innermost():
    assert scope_of("jit(step)/while/body/attn/cim/dot_general") == "cim"
    assert scope_of("jit(step)/while/body/attn/mul") == "attn"
    assert scope_of("jit(pf)/fill.merge/select_n") == "fill.merge"
    assert scope_of("jit(step)/while/body/dynamic_update_slice") is None
    assert scope_of("caches.k") is None
    assert set(SCOPES) == {"attn", "ssm", "cim", "unembed", "sample", "fill.merge",
                           "kv.write"}


def test_hlo_op_names_reads_full_instruction_names():
    text = """HloModule jit_step
%fused_computation (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %multiply.3 = f32[2]{0} multiply(%p, %p), metadata={op_name="jit(step)/attn/mul" source_file="a.py" source_line=3}
}
ENTRY %main (a: f32[2]) -> f32[2] {
  %a = f32[2]{0} parameter(0), metadata={op_name="caches.k"}
  %copy.7 = f32[2]{0} copy(%a), metadata={op_name="caches.k"}
  ROOT %fusion.1 = f32[2]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/attn/mul"}
}"""
    names = hlo_op_names(text)
    assert names == {"%p": "", "%multiply.3": "jit(step)/attn/mul",
                     "%a": "caches.k", "%copy.7": "caches.k",
                     "%fusion.1": "jit(step)/attn/mul"}
