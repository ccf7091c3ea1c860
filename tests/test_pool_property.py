"""Hypothesis property tests for the ref-counted COW PagePool: random
alloc / ensure / fork / prepare_write / publish / free / pressure-evict
interleavings must keep ``check_invariants`` green after every op —
refcounts equal table references, prefix-cache-held pages are unreferenced
by live sequences, no page is simultaneously free and mapped, and a
COW-prepared write range is always exclusively owned by the writer.
"""
import numpy as np
import pytest

from repro.serving import PagePool, PagePoolOOM, chain_hashes

P = 4  # page size

# ---------------------------------------------------------------------------
# hypothesis property test: random interleavings vs invariants
# ---------------------------------------------------------------------------
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st  # noqa: E402


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.data())
def test_pool_random_interleavings_keep_invariants(data):
    """alloc / ensure / fork / prepare_write / publish / free / pressure-
    evict in random order: after every op the pool invariants hold —
    refcounts equal table references, cache-held pages are unreferenced,
    no page is both free and mapped, and a COW-prepared range is always
    exclusively owned (refcount 1) by the writer."""
    pool = PagePool(num_pages=data.draw(st.integers(6, 20), label="pages"),
                    page_size=P, prefix_cache=True)
    streams = {}                     # seq -> (tokens, hashes)
    next_seq = 0
    for _ in range(data.draw(st.integers(5, 30), label="ops")):
        live = sorted(streams)
        op = data.draw(st.sampled_from(
            ["alloc", "ensure", "fork", "write", "publish", "free",
             "truncate"]))
        try:
            if op == "alloc":
                n = data.draw(st.integers(1, 3 * P))
                toks = np.asarray(data.draw(st.lists(
                    st.integers(0, 2), min_size=n, max_size=n)), np.int32)
                hashes = chain_hashes(b"ns", toks, P)
                cached = pool.match_pages(hashes[:max(0, (n - 1) // P)])
                fresh = pool.pages_for(n) - len(cached)
                pool.alloc_pages(next_seq, fresh, owner=next_seq % 2,
                                 cached=cached)
                streams[next_seq] = (toks, hashes)
                next_seq += 1
            elif op == "ensure" and live:
                seq = data.draw(st.sampled_from(live))
                toks, _ = streams[seq]
                extra = data.draw(st.integers(1, P + 1))
                grown = np.concatenate(
                    [toks, np.zeros((extra,), np.int32)])
                pool.ensure(seq, len(grown))
                streams[seq] = (grown, chain_hashes(b"ns", grown, P))
            elif op == "fork" and live:
                src = data.draw(st.sampled_from(live))
                pool.fork(src, next_seq, owner=next_seq % 2)
                streams[next_seq] = streams[src]
                next_seq += 1
            elif op == "write" and live:
                seq = data.draw(st.sampled_from(live))
                table = pool.table(seq)
                if table:
                    hi = len(table) * P
                    a = data.draw(st.integers(0, hi - 1))
                    b = data.draw(st.integers(a + 1, hi))
                    pool.prepare_write(seq, a, b)
                    for i in range(a // P, pool.pages_for(b)):
                        page = pool.table(seq)[i]
                        assert pool.refcount(page) == 1, \
                            "COW left a written page shared"
            elif op == "publish" and live:
                seq = data.draw(st.sampled_from(live))
                toks, hashes = streams[seq]
                pool.publish_prefix(seq, hashes, len(hashes))
            elif op == "free" and live:
                seq = data.draw(st.sampled_from(live))
                pool.free_seq(seq)
                del streams[seq]
            elif op == "truncate" and live:
                # speculative partial-accept rollback: drop the tail
                seq = data.draw(st.sampled_from(live))
                toks, _ = streams[seq]
                keep = data.draw(st.integers(0, max(0, len(toks))))
                pool.truncate_seq(seq, keep,
                                  recredit=data.draw(st.booleans()))
                kept = toks[:pool.pages_for(keep) * P] if keep else toks[:0]
                streams[seq] = (kept, chain_hashes(b"ns", kept, P))
        except PagePoolOOM:
            pass                      # legal outcome under pressure
        pool.check_invariants()
    for seq in sorted(streams):
        pool.free_seq(seq)
    pool.check_invariants()
    assert pool.used_pages == 0


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_pool_cow_never_touches_shared_pages(data):
    """The issue's refcount invariant, stated directly: after
    ``prepare_write`` the written range is exclusively owned, and every
    page another sequence still maps kept its refcount and its bytes
    (same page id in the other table)."""
    pool = PagePool(num_pages=16, page_size=P, prefix_cache=True)
    n = data.draw(st.integers(1, 4)) * P
    pool.alloc(0, n)
    forks = data.draw(st.integers(1, 3))
    for f in range(1, forks + 1):
        pool.fork(0, f)
    before = {s: pool.table(s) for s in range(forks + 1)}
    writer = data.draw(st.integers(0, forks))
    a = data.draw(st.integers(0, n - 1))
    pool.prepare_write(writer, a, n)
    for s in range(forks + 1):
        if s == writer:
            continue
        assert pool.table(s) == before[s], "COW mutated a reader's table"
    for i in range(a // P, n // P):
        assert pool.refcount(pool.table(writer)[i]) == 1
    pool.check_invariants()


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_fork_then_partial_rollback_releases_only_the_tail(data):
    """The speculative-decode lifecycle: fork a published prefix, COW the
    tail for draft writes, then roll a rejected tail back with
    ``truncate_seq`` — the reader's table is untouched, only tail pages
    are released, and under ``recredit`` the freed pages stay promised to
    the writer (its later re-grow can never lose them to a bystander)."""
    pool = PagePool(num_pages=16, page_size=P, prefix_cache=True)
    n_pages = data.draw(st.integers(2, 4), label="pages")
    n = n_pages * P
    toks = np.asarray(data.draw(st.lists(st.integers(0, 2),
                                         min_size=n, max_size=n)), np.int32)
    hashes = chain_hashes(b"ns", toks, P)
    pool.alloc(0, n)
    pool.publish_prefix(0, hashes, n_pages)
    pool.fork(0, 1)                       # the speculating sequence
    spec_end = n + data.draw(st.integers(1, 2 * P), label="drafted")
    pool.ensure(1, spec_end)              # draft tail pages
    pool.prepare_write(1, n - 1, spec_end)
    reader_before = pool.table(0)
    used_before = pool.used_pages
    keep = data.draw(st.integers(n, spec_end), label="accepted")
    recredit = data.draw(st.booleans(), label="recredit")
    released = pool.truncate_seq(1, keep, recredit=recredit)
    pool.check_invariants()
    assert pool.table(0) == reader_before, "rollback mutated the reader"
    assert released == pool.pages_for(spec_end) - pool.pages_for(keep)
    assert pool.used_pages == used_before - released
    if recredit:
        assert pool.deferred_pages == released
        # the promise is redeemable even after a bystander drains the
        # free list: the writer re-grows to where it was, OOM-free
        grabber = 2
        free_now = pool.free_pages - pool.deferred_pages
        if free_now:
            pool.alloc_pages(grabber, free_now)
        pool.ensure(1, spec_end)
        assert pool.deferred_pages == 0
        pool.check_invariants()



# ---------------------------------------------------------------------------
# int8 paged-KV properties: quantization roundtrip bound, and scale rows
# traveling with their pages through COW / fork / truncate page copies
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(st.data())
def test_int8_roundtrip_error_within_quantization_step(data):
    """quantize_int8(axis=(2, 3)) -> dequantize: every element of a
    [P, KH, psize, D] pool must come back within its (page, head)'s
    quantization step, amax / 127 (symmetric rounding: half a step plus
    float slop; one full step is a safe outer bound)."""
    from repro.optim.compression import dequantize_int8, quantize_int8

    P_, psize, KH, D = (data.draw(st.integers(1, 4), label="P"),
                        data.draw(st.sampled_from([2, 4]), label="psize"),
                        data.draw(st.integers(1, 3), label="KH"),
                        data.draw(st.sampled_from([4, 8]), label="D"))
    scale_mag = data.draw(st.sampled_from([1e-3, 1.0, 100.0]), label="mag")
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31), label="s"))
    x = np.asarray(rng.normal(size=(P_, KH, psize, D)) * scale_mag,
                   np.float32)
    q, sc = quantize_int8(x, axis=(2, 3))
    back = np.asarray(dequantize_int8(q, sc))
    step = np.abs(x).max(axis=(2, 3), keepdims=True) / 127.0
    assert (np.abs(back - x) <= step + 1e-9).all()
    assert np.asarray(q).dtype == np.int8
    assert sc.shape == (P_, KH, 1, 1)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_page_copy_moves_scales_with_pages(data):
    """The device-side COW page copy on an int8 cache: after copying
    src[i] -> dst[i], the *dequantized* dst page equals the dequantized
    src page — i.e. the scale sidecar rows traveled with their pages
    (fork / prefix-cache publish / preemption restore never split a page
    from its scale).  Checked for both the plain [P, ...] leaf layout and
    the scanned [R, P, ...] superblock layout."""
    import jax.numpy as jnp
    from repro.core.steps import make_page_copy_step
    from repro.optim.compression import quantize_int8

    psize, KH, D, NP = 4, 2, 4, 8
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31), label="s"))
    scanned = data.draw(st.booleans(), label="scanned")
    shape = (2, NP, KH, psize, D) if scanned else (NP, KH, psize, D)
    x = np.asarray(rng.normal(size=shape), np.float32)
    ax = (3, 4) if scanned else (2, 3)
    q, sc = quantize_int8(jnp.asarray(x), axis=ax)
    sc = sc[..., 0, 0]
    n = data.draw(st.integers(1, 4), label="copies")
    src = data.draw(st.lists(st.integers(1, NP - 1), min_size=n, max_size=n),
                    label="src")
    # distinct dst pages (a page is only ever COW-copied onto a free page)
    dst = data.draw(st.permutations(list(range(1, NP))), label="dst")[:n]

    def deq(pool, scale):
        pool, scale = np.asarray(pool, np.float32), np.asarray(scale)
        return pool * scale[..., None, None]

    before = deq(q, sc)              # snapshot first: the copy donates its
    copy = make_page_copy_step()     # cache argument (in-place on device)
    (q2, sc2), = copy([(q, sc)], jnp.asarray(src, jnp.int32),
                      jnp.asarray(dst, jnp.int32))
    after = deq(q2, sc2)
    want = before.copy()
    for s_, d_ in zip(src, dst):            # later copies win, like x.at[]
        if scanned:
            want[:, d_] = before[:, s_]
        else:
            want[d_] = before[s_]
    untouched = [p for p in range(NP) if p not in dst]
    sel = (slice(None),) if scanned else ()
    for p in untouched:
        assert np.array_equal(after[sel + (p,)], want[sel + (p,)])
    for s_, d_ in zip(src, dst):
        assert np.array_equal(after[sel + (d_,)], want[sel + (d_,)]), \
            "scale row did not travel with its page"


def test_pool_fork_and_truncate_preserve_scale_correspondence():
    """Host-side lifecycle: PagePool fork shares page *ids* (scales are
    indexed by page id, so correspondence is automatic), COW prepare_write
    gives the writer fresh ids — and the engine copies pool+scale rows to
    the new ids together (test above) — and truncate_seq only drops tail
    ids, never remapping survivors."""
    pool = PagePool(num_pages=16, page_size=P, prefix_cache=True)
    pool.alloc(0, 3 * P)
    t0 = pool.table(0)
    pool.fork(0, 1)
    assert pool.table(1) == t0              # shared ids -> shared scales
    pool.prepare_write(1, P, 3 * P)         # COW the tail
    t1 = pool.table(1)
    assert t1[0] == t0[0]                   # untouched head still shared
    assert t1[1] != t0[1] and t1[2] != t0[2]
    pool.truncate_seq(1, 2 * P)
    assert pool.table(1) == t1[:2]          # survivors keep their ids
    pool.check_invariants()
