"""Speculative decoding equivalence: spec-on token streams must be
BITWISE identical to plain greedy decode across the full feature matrix
(prefix cache, packed prefill, overlapped transfers), including
mid-speculation preemption and a draft that disagrees with the target —
greedy verify re-derives every emitted token from the target argmax, so
the draft can only change WHEN tokens appear, never WHICH.

Kernel level: every packed-verify row must be bitwise-equal to
``paged_decode_attention`` run with that row's gathered block table (the
contract the engine guarantee rests on), and allclose to the naive
softmax oracle in ref.py (online softmax rounds differently, same as the
other attention kernels)."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import EngineConfig, Request, SLO, make_policy
from repro.kernels import packed_verify_attention, paged_decode_attention
from repro.kernels.paged_attention import pages_per_block
from repro.kernels.ref import packed_verify_attention_ref
from repro.models import init_params
from repro.serving import Engine


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("qwen1_5_0_5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    draft_params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params, draft_params


def _run(cfg, params, *, spec_k=0, draft=None, n=6, plen=48,
         num_blocks=256, prefix_cache=True, packed=True, overlap=True,
         prio=1, out_lo=3, out_hi=9):
    rng = np.random.default_rng(0)
    kw = {}
    if spec_k:
        kw["spec_draft"] = draft
    eng = Engine(cfg, params,
                 EngineConfig(eta=1.0, w_p=4.0, tau=1e9, spec_k=spec_k),
                 make_policy("slidebatching"), num_blocks=num_blocks,
                 block_size=16, max_ctx=512, prefix_cache=prefix_cache,
                 packed_prefill=packed, overlap_transfers=overlap, **kw)
    trace = []
    for _ in range(n):
        r = Request(prompt_len=plen,
                    output_len=int(rng.integers(out_lo, out_hi)),
                    arrival=0.0, slo=SLO(3600.0, 3600.0), priority=prio)
        trace.append(r)
        eng.add_request(r, rng.integers(1, cfg.vocab, plen).astype(np.int32))
    eng.run_until_drained(max_iters=2000)
    outs = {i: eng.outputs[r.rid] for i, r in enumerate(trace)}
    stats = eng.stats
    eng.kill()
    return outs, stats


@pytest.fixture(scope="module")
def reference(model):
    cfg, params, _ = model
    outs, _ = _run(cfg, params)
    return outs


@pytest.mark.slow
@pytest.mark.parametrize("prefix_cache,packed,overlap", [
    (True, True, True),
    (False, True, False),
    (True, False, True),
])
def test_spec_stream_matrix(model, reference, prefix_cache, packed, overlap):
    """Spec on, same-params draft (full acceptance — maximum speculative
    writes) across engine feature combos: streams bitwise-identical to
    the plain-decode reference, with real speculation happening."""
    cfg, params, _ = model
    outs, st = _run(cfg, params, spec_k=2, draft=(cfg, params),
                    prefix_cache=prefix_cache, packed=packed,
                    overlap=overlap)
    assert outs == reference
    assert st.spec_proposed > 0
    assert st.spec_accepted == st.spec_proposed    # same params: all match
    assert st.spec_proposed == st.spec_accepted + st.spec_rejected
    assert max(st.spec_depth_hist) == 2            # priority 1: full depth


@pytest.mark.slow
def test_spec_rejecting_draft_stream_identical(model, reference):
    """A draft with different weights proposes garbage; greedy verify
    rejects it and the stream stays bitwise-identical (only throughput,
    never content, depends on draft quality)."""
    cfg, params, draft_params = model
    outs, st = _run(cfg, params, spec_k=2, draft=(cfg, draft_params))
    assert outs == reference
    assert st.spec_rejected > 0
    assert st.spec_proposed == st.spec_accepted + st.spec_rejected
    # rejections crash the acceptance EWMA -> the controller collapses
    # depth toward 0 instead of burning verify rows
    assert st.spec_depth_hist.get(0, 0) > 0


@pytest.mark.slow
def test_spec_preemption_mid_stream(model):
    """Memory pressure forces evictions while requests are mid-decode
    with live draft state: preempted requests drop their draft context,
    re-engage after reload, and still emit the exact reference stream."""
    cfg, params, _ = model
    base, _ = _run(cfg, params, n=8, num_blocks=28, out_lo=6, out_hi=12)
    outs, st = _run(cfg, params, spec_k=2, draft=(cfg, params), n=8,
                    num_blocks=28, out_lo=6, out_hi=12)
    assert outs == base
    assert st.evictions > 0, "config must actually force preemption"
    assert st.spec_proposed > 0


def test_spec_counters_and_launch_accounting(model):
    cfg, params, _ = model
    outs, st = _run(cfg, params, spec_k=2, draft=(cfg, params))
    assert st.spec_proposed == st.spec_accepted + st.spec_rejected
    # every decode entry lands in the depth histogram
    assert sum(st.spec_depth_hist.values()) > 0
    assert st.draft_launches > 0
    # accepted bonus tokens shrink the launch count vs one-per-token
    total_out = sum(len(v) for v in outs.values())
    assert st.decode_launches + st.spec_accepted <= total_out
    # one host fetch per target launch; draft decode rounds add at most
    # draft_launches more (draft prefill ingests don't fetch)
    target = st.decode_launches + st.packed_prefill_calls
    assert target <= st.host_syncs <= target + st.draft_launches


def test_spec_requires_draft(model):
    cfg, params, _ = model
    with pytest.raises(ValueError):
        Engine(cfg, params, EngineConfig(spec_k=2),
               make_policy("slidebatching"), num_blocks=64)


# ---------------------------------------------------------------------------
# kernel-level contract
# ---------------------------------------------------------------------------

@pytest.mark.kernel
def test_packed_verify_kernel_contract():
    """Row-for-row the packed kernel must be BITWISE equal to the plain
    paged-decode kernel run with gathered per-row tables (same body, same
    accumulation order) and allclose to the naive softmax oracle; with a
    row that spans more than one of the kernel's page blocks, and a row
    of length 0 (which attends to nothing and returns zeros)."""
    key = jax.random.PRNGKey(3)
    page, hkv, g, hd = 8, 2, 4, 16
    n_pages, maxp, n_seg = 24, 6, 3
    depth = 2
    k1, k2, k3 = jax.random.split(key, 3)
    k_pages = jax.random.normal(k1, (n_pages, hkv, page, hd), jax.numpy.float32)
    v_pages = jax.random.normal(k2, (n_pages, hkv, page, hd), jax.numpy.float32)
    rng = np.random.default_rng(5)
    tables = rng.permutation(np.arange(1, n_pages))[:n_seg * maxp]
    tables = tables.reshape(n_seg, maxp).astype(np.int32)
    # rows: (seg, j) for j = 0..depth; per-row length l_kv + j + 1; then
    # one row of length 0
    base = np.array([9, 14, 40], np.int32)
    row_seg = np.append(np.repeat(np.arange(n_seg, dtype=np.int32),
                                  depth + 1), np.int32(1))
    lengths = np.concatenate(
        [b + np.arange(depth + 1, dtype=np.int32) + 1 for b in base]
        + [np.zeros(1, np.int32)])
    span = page * pages_per_block(maxp, hkv, page, hd, jax.numpy.float32)
    assert lengths.max() > span
    q = jax.random.normal(k3, (len(row_seg), hkv * g, hd), jax.numpy.float32)

    out = packed_verify_attention(q, k_pages, v_pages,
                                  jax.numpy.asarray(tables),
                                  jax.numpy.asarray(lengths),
                                  jax.numpy.asarray(row_seg), interpret=True)
    gathered = paged_decode_attention(
        q, k_pages, v_pages, jax.numpy.asarray(tables[row_seg]),
        jax.numpy.asarray(lengths), interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(gathered))
    ref = packed_verify_attention_ref(q, k_pages, v_pages,
                                      jax.numpy.asarray(tables),
                                      jax.numpy.asarray(lengths),
                                      jax.numpy.asarray(row_seg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out[-1]).any()
