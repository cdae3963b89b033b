"""Wire-format codec + session tests (repro.api, DESIGN.md §7)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import codecs
from repro.api.session import FLClient, FLSession, ServeSession
from repro.core.omc import OMCConfig
from repro.core.policy import QuantizePolicy
from repro.core.store import (CompressedVariable, compress_tree,
                              compress_variable, is_compressed)
from repro.data.synthetic import make_lm_task
from repro.federated.cohort import CohortPlan
from repro.federated.state import state_bytes_report
from repro.kernels import ref
from repro.models import transformer as tr
from repro.models.common import IDENTITY_MAT

# one format per uint container: u8 (6 bits), u16 (11), u32 (19)
FORMATS = ["S1E2M3", "S1E3M7", "S1E4M14"]
POLICY = QuantizePolicy(min_size=64)


def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return dict(
        emb=jax.random.normal(key, (64, 32)) * 0.02,
        blocks=[
            dict(
                w=jax.random.normal(jax.random.fold_in(key, i), (32, 32)),
                scale=jnp.ones((32,)),  # 1-D: stays raw f32
            )
            for i in range(3)
        ],
    )


def assert_trees_bit_equal(a_tree, b_tree):
    a_flat = jax.tree_util.tree_flatten_with_path(a_tree, is_leaf=is_compressed)[0]
    b_flat = jax.tree_util.tree_flatten_with_path(b_tree, is_leaf=is_compressed)[0]
    assert len(a_flat) == len(b_flat)
    for (pa, a), (pb, b) in zip(a_flat, b_flat):
        assert pa == pb
        if is_compressed(a):
            assert is_compressed(b)
            assert a.fmt == b.fmt
            assert b.codes.dtype == a.codes.dtype
            np.testing.assert_array_equal(np.asarray(a.codes), np.asarray(b.codes))
            np.testing.assert_array_equal(np.asarray(a.s), np.asarray(b.s))
            np.testing.assert_array_equal(np.asarray(a.b), np.asarray(b.b))
        else:
            assert not is_compressed(b)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fmt", FORMATS)
def test_roundtrip_bit_exact(fmt):
    """decode(encode(compress_tree(t))) == compress_tree(t), code-for-code."""
    omc = OMCConfig.parse(fmt, policy=POLICY)
    ct = compress_tree(_tree(), omc.fmt, omc.policy)
    back, info = codecs.decode_payload(codecs.encode_payload(ct, round_index=7))
    assert_trees_bit_equal(ct, back)
    assert info.round_index == 7
    assert not info.is_delta
    assert info.num_compressed == 4  # emb + 3 block matrices


@pytest.mark.parametrize("fmt", FORMATS)
def test_chunked_pack_is_byte_identical(fmt, monkeypatch):
    """Leaves larger than one device call are packed and unpacked chunk by
    chunk: the payload stays byte-identical and decodes code-for-code, for
    full leaves, odd tails and delta leaves alike."""
    omc = OMCConfig.parse(fmt, policy=POLICY)
    ct = compress_tree(_tree(), omc.fmt, omc.policy)
    t2 = dict(_tree())
    t2["emb"] = t2["emb"].at[:3].add(0.5)
    ct2 = compress_tree(t2, omc.fmt, omc.policy)
    whole = codecs.encode_payload(ct)
    whole_delta = codecs.encode_payload(ct2, base=ct)
    monkeypatch.setattr(codecs, "_CHUNK_FIELDS", 96)  # 2048 = 21*96 + 32
    assert codecs.encode_payload(ct) == whole
    assert codecs.encode_payload(ct2, base=ct) == whole_delta
    assert_trees_bit_equal(ct, codecs.decode_payload(whole)[0])
    assert_trees_bit_equal(ct2, codecs.decode_payload(whole_delta, base=ct)[0])


CHUNK = 96  # fields per device call in the tests below


def _leaf(fmt: str, shape, per_layer: bool, seed=0):
    """A compressed leaf with 0-d or per-layer (leading axis) s and b."""
    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    return compress_variable(x, OMCConfig.parse(fmt).fmt, batch_axes=int(per_layer))


def _oracle_words(codes, bits: int) -> np.ndarray:
    """The canonical stream as the host-side codec built it: the jnp oracle
    packs each ``CHUNK``-field slice of a numpy copy, words joined."""
    flat = np.asarray(codes).reshape(-1)
    return np.concatenate([np.asarray(ref.ref_pack(flat[i:i + CHUNK], bits), np.uint32)
                           for i in range(0, flat.size, CHUNK)])


def _body(payload: bytes) -> bytes:
    return payload[codecs.peek_payload(payload).header_bytes:]


def _sb_bytes(cv) -> bytes:
    return b"".join(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes()
                    for a in (cv.s, cv.b))


@pytest.mark.parametrize("per_layer", [False, True], ids=["scalar_sb", "per_layer_sb"])
@pytest.mark.parametrize("fmt,shape", [
    ("S1E3M7", (3, 20)),    # under one chunk
    ("S1E3M7", (3, 32)),    # exactly one chunk
    ("S1E3M7", (3, 96)),    # three whole chunks
    ("S1E3M7", (3, 100)),   # three chunks and a ragged 12
    ("S1E3M7", (3, 1000)),  # 32 chunks: two cast groups, the last chunk ragged
    ("S1E2M3", (3, 100)),   # u8 container
    ("S1E4M14", (3, 100)),  # u32 container
], ids=["under", "one", "whole", "ragged", "groups", "ragged_u8", "ragged_u32"])
def test_full_payload_matches_host_oracle(fmt, shape, per_layer, monkeypatch):
    """A full leaf packed on the device gives the host codec's bytes, and
    decodes to device codes of the container dtype."""
    monkeypatch.setattr(codecs, "_CHUNK_FIELDS", CHUNK)
    cv = _leaf(fmt, shape, per_layer)
    assert np.size(cv.s) == (3 if per_layer else 1)
    payload = codecs.encode_payload(dict(w=cv))
    assert _body(payload) == _sb_bytes(cv) + _oracle_words(cv.codes, cv.fmt.bits).tobytes()
    back = codecs.decode_payload(payload)[0]["w"]
    assert isinstance(back.codes, jax.Array)
    assert back.codes.dtype == cv.fmt.container_dtype
    assert_trees_bit_equal(dict(w=cv), dict(w=back))


def test_delta_payload_matches_host_oracle(monkeypatch):
    """A delta leaf is still found and packed from host codes: sorted
    indices, then the XOR of the changed codes packed chunk by chunk."""
    monkeypatch.setattr(codecs, "_CHUNK_FIELDS", CHUNK)
    base = _leaf("S1E3M7", (3, 1000), per_layer=True)
    codes = np.asarray(base.codes).copy()
    changed = np.arange(0, 3000, 15)  # 200 codes: three chunks, the last ragged
    codes.reshape(-1)[changed] ^= 0x55
    new = CompressedVariable(jnp.asarray(codes), base.s, base.b, base.fmt)
    payload = codecs.encode_payload(dict(w=new), base=dict(w=base))
    xor = (codes.reshape(-1) ^ np.asarray(base.codes).reshape(-1)).astype(np.uint32)
    assert _body(payload) == (_sb_bytes(new) + changed.astype(np.uint32).tobytes()
                              + _oracle_words(xor[changed], new.fmt.bits).tobytes())
    back, info = codecs.decode_payload(payload, base=dict(w=base))
    assert info.is_delta
    assert_trees_bit_equal(dict(w=new), back)


def test_decode_unpacks_through_unpack_np(monkeypatch):
    """A full leaf's codes come from ``_unpack_np``, which may hand back a
    host array: one flipped field there is one changed decoded code."""
    cv = _leaf("S1E3M7", (3, 100), per_layer=False)
    real = codecs._unpack_np

    def flipped(words, bits, n):
        out = np.array(real(words, bits, n))
        out[0] ^= 1
        return out

    monkeypatch.setattr(codecs, "_unpack_np", flipped)
    back = codecs.decode_payload(codecs.encode_payload(dict(w=cv)))[0]["w"]
    diff = np.asarray(back.codes) != np.asarray(cv.codes)
    assert diff.sum() == 1 and diff.reshape(-1)[0]


def _compiles(caplog, fn) -> int:
    """Programs JAX traces and compiles while ``fn`` runs."""
    caplog.clear()
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        jax.block_until_ready(fn())
    return sum(r.getMessage().startswith("Compiling ") for r in caplog.records)


def test_codec_programs_do_not_grow_with_chunks(monkeypatch, caplog):
    """A round trip compiles a fixed set of programs for a leaf shape,
    however many chunks the leaf has, and a second one compiles nothing."""
    monkeypatch.setattr(codecs, "_CHUNK_FIELDS", CHUNK)
    counts = []
    for chunks in (20, 40):  # whole chunks, then a ragged 40: two, three groups
        tree = dict(w=_leaf("S1E3M7", (chunks * CHUNK + 40,), per_layer=False))
        jax.clear_caches()

        def trip():
            return codecs.decode_payload(codecs.encode_payload(tree))[0]

        counts.append(_compiles(caplog, trip))
        assert _compiles(caplog, trip) == 0
    assert counts[0] == counts[1] > 0


def test_encode_and_digest_release_leaves_without_gc():
    """Walking a tree leaves no reference cycle behind: a dropped tree's
    leaves are freed at once, not when the garbage collector next runs (a
    model's codes would otherwise stay on the device after an encode, a
    digest or a hot swap)."""
    import gc
    import weakref

    tree = dict(a=np.arange(64, dtype=np.float32),
                b=[np.ones(3, np.float32), (np.zeros(2, np.float32),)])
    refs = [weakref.ref(x) for x in (tree["a"], tree["b"][0], tree["b"][1][0])]
    gc.disable()
    try:
        codecs.encode_payload(tree)
        codecs.tree_digest(tree)
        codecs.decode_payload(codecs.encode_payload(tree), base=tree)
        del tree
        assert all(r() is None for r in refs)
    finally:
        gc.enable()


@pytest.mark.parametrize("fmt", FORMATS)
def test_body_bytes_reconcile_with_store_accounting(fmt):
    omc = OMCConfig.parse(fmt, policy=POLICY)
    ct = compress_tree(_tree(), omc.fmt, omc.policy)
    info = codecs.peek_payload(codecs.encode_payload(ct))
    rep = codecs.payload_bytes_report(ct)
    assert rep["wire_bytes"] == state_bytes_report(ct)["packed_bytes"]
    assert info.body_bytes == rep["wire_bytes"]


def test_delta_identity_and_size():
    """apply(delta(a, b), a) == b bit-exactly; sparse delta beats full."""
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    t1 = _tree()
    t2 = dict(t1)
    t2["emb"] = t1["emb"].at[0, :4].add(0.5)  # few codes change
    a = compress_tree(t1, omc.fmt, omc.policy)
    b = compress_tree(t2, omc.fmt, omc.policy)
    delta = codecs.encode_payload(b, base=a)
    full = codecs.encode_payload(b)
    back, info = codecs.decode_payload(delta, base=a)
    assert info.is_delta
    assert_trees_bit_equal(b, back)
    assert len(delta) < len(full) // 4


def test_delta_never_worse_than_full():
    """A fully-changed tree falls back to per-leaf full encoding."""
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    a = compress_tree(_tree(0), omc.fmt, omc.policy)
    b = compress_tree(_tree(1), omc.fmt, omc.policy)  # unrelated values
    delta = codecs.encode_payload(b, base=a)
    full = codecs.encode_payload(b)
    back, _ = codecs.decode_payload(delta, base=a)
    assert_trees_bit_equal(b, back)
    assert len(delta) <= len(full) + 64 * 4  # at most per-leaf mode metadata


def test_delta_requires_base():
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    a = compress_tree(_tree(0), omc.fmt, omc.policy)
    t2 = dict(_tree(0))
    t2["emb"] = t2["emb"].at[0, 0].add(0.5)
    b = compress_tree(t2, omc.fmt, omc.policy)
    delta = codecs.encode_payload(b, base=a)
    with pytest.raises(codecs.CodecError):
        codecs.decode_payload(delta)


def test_delta_wrong_base_rejected_by_digest():
    """Applying a delta to a same-shaped but different tree must fail loudly
    (silent wrong-base XOR would hand the receiver the wrong model)."""
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    a = compress_tree(_tree(0), omc.fmt, omc.policy)
    wrong = compress_tree(_tree(1), omc.fmt, omc.policy)  # same shapes
    t2 = dict(_tree(0))
    t2["emb"] = t2["emb"].at[0, 0].add(0.5)
    b = compress_tree(t2, omc.fmt, omc.policy)
    delta = codecs.encode_payload(b, base=a)
    with pytest.raises(codecs.CodecError, match="base mismatch"):
        codecs.decode_payload(delta, base=wrong)
    # the right base still decodes bit-exactly
    back, _ = codecs.decode_payload(delta, base=a)
    assert_trees_bit_equal(b, back)


def test_tuple_containers_roundtrip():
    """Tuples must come back as tuples — hot_swap relies on an unchanged
    treedef to avoid retracing."""
    key = jax.random.PRNGKey(3)
    t = dict(
        pair=(jax.random.normal(key, (16, 16)),
              jax.random.normal(jax.random.fold_in(key, 1), (16, 16))),
        lst=[jax.random.normal(jax.random.fold_in(key, 2), (16, 16))],
    )
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    ct = compress_tree(t, omc.fmt, omc.policy)
    back, _ = codecs.decode_payload(codecs.encode_payload(ct))
    assert isinstance(back["pair"], tuple)
    assert isinstance(back["lst"], list)
    assert (jax.tree_util.tree_structure(ct, is_leaf=is_compressed)
            == jax.tree_util.tree_structure(back, is_leaf=is_compressed))
    assert_trees_bit_equal(ct, back)


def test_corrupt_payload_rejected():
    omc = OMCConfig.parse("S1E3M7", policy=POLICY)
    buf = bytearray(
        codecs.encode_payload(compress_tree(_tree(), omc.fmt, omc.policy))
    )
    for pos in (6, len(buf) // 2, len(buf) - 1):  # header, manifest/body, tail
        bad = bytearray(buf)
        bad[pos] ^= 0xFF
        with pytest.raises(codecs.CodecError):
            codecs.decode_payload(bytes(bad))
    with pytest.raises(codecs.CodecError):
        codecs.decode_payload(bytes(buf[: len(buf) // 2]))  # truncated


def test_version_negotiation():
    assert codecs.negotiate_version([1, 5, 9]) == 1
    with pytest.raises(codecs.CodecError):
        codecs.negotiate_version([99])


CFG = tr.TransformerConfig(
    n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=128
)


def _make_clients(omc, task, lr=0.05):
    @jax.jit
    def sgd(params, batch):
        _, g = jax.value_and_grad(
            lambda p: tr.loss(CFG, p, batch, IDENTITY_MAT)
        )(params)
        return jax.tree_util.tree_map(lambda w, gg: w - lr * gg, params, g)

    def train_fn(params, cid, r):
        return sgd(params, task.batch(cid, r, 0, 2))

    return {c: FLClient(c, tr, CFG, omc, train_fn) for c in range(4)}


def test_fl_session_two_round_loopback():
    """2 rounds of download -> train -> upload -> aggregate over the wire."""
    omc = OMCConfig.parse("S1E3M7")
    task = make_lm_task(vocab=CFG.vocab, seq_len=16, num_clients=4)
    sess = FLSession(
        tr, CFG, omc, plan=CohortPlan(num_clients=4, cohort_size=2)
    )
    clients = _make_clients(omc, task)

    def first_cv_codes(tree):
        return np.asarray(next(
            l for l in jax.tree_util.tree_leaves(tree, is_leaf=is_compressed)
            if is_compressed(l)
        ).codes)

    before = first_cv_codes(sess.storage).copy()
    for r in range(2):
        ticket = sess.begin_round()
        assert ticket.round_index == r
        assert len(ticket.client_ids) == 2
        assert (ticket.delta_payload is not None) == (r > 0)
        for cid in ticket.client_ids:
            info = sess.ingest(cid, clients[cid].run_round(ticket))
            assert info.total_bytes > 0
        assert len(ticket.issued_bytes) == 2
        metrics = sess.close_round()
        assert metrics["reports"] == 2
    assert sess.round_index == 2
    after = first_cv_codes(sess.storage)
    assert (before != after).any()  # training actually moved the model
    # compressed download stayed under the paper's ~59%-reduction envelope
    t = sess.traffic
    assert t["down_bytes"] <= 0.60 * t["down_fp32_bytes"]


def test_client_delta_choice_by_cache_digest():
    """A client whose cache matches round r-1 takes the delta download; a
    client with a stale cache (skipped a round) falls back to full."""
    omc = OMCConfig.parse("S1E3M7")
    task = make_lm_task(vocab=CFG.vocab, seq_len=16, num_clients=4)
    sess = FLSession(tr, CFG, omc)  # plan=None: client 0 every round
    fresh = _make_clients(omc, task)[0]
    stale = _make_clients(omc, task)[0]

    # round 0: both decode the full payload (no cache yet)
    ticket = sess.begin_round()
    sess.ingest(0, fresh.run_round(ticket))
    stale.run_round(ticket)  # participates but we only ingest one report
    assert ticket.issued_bytes == [len(ticket.payload)] * 2
    sess.close_round()

    # round 1: only `fresh` participates; its cache == round-0 model == the
    # delta base, so it takes the delta
    ticket = sess.begin_round()
    sess.ingest(0, fresh.run_round(ticket))
    assert ticket.issued_bytes == [len(ticket.delta_payload)]
    sess.close_round()

    # round 2: `stale` last saw round 0; the delta base is the round-1 model,
    # so the digest mismatches and it must take the full payload
    ticket = sess.begin_round()
    sess.ingest(0, stale.run_round(ticket))
    assert ticket.issued_bytes == [len(ticket.payload)]
    sess.close_round()


def test_fl_session_guards():
    omc = OMCConfig.parse("S1E3M7")
    sess = FLSession(tr, CFG, omc, plan=CohortPlan(num_clients=4, cohort_size=2))
    with pytest.raises(RuntimeError):
        sess.ingest(0, b"")
    ticket = sess.begin_round()
    with pytest.raises(RuntimeError):
        sess.begin_round()
    outsider = [c for c in range(4) if c not in ticket.client_ids][0]
    with pytest.raises(KeyError):
        sess.ingest(outsider, b"")
    with pytest.raises(RuntimeError):
        sess.close_round()  # zero reports


def test_serve_session_hot_swap_bit_transparent():
    """hot_swap(encode(storage)) leaves the served tree bit-identical."""
    omc = OMCConfig.parse("S1E3M7")
    sess = FLSession(tr, CFG, omc)
    serve = ServeSession(tr, CFG, sess.storage)
    payload = sess.server_payload()
    info = serve.hot_swap(payload)
    assert not info.is_delta
    assert_trees_bit_equal(sess.storage, serve.storage)
    cache = serve.init_cache(1, 16)
    _, gen = serve.generate(dict(tokens=jnp.zeros((1, 4), jnp.int32)), cache, 3)
    assert gen.shape == (1, 3)
