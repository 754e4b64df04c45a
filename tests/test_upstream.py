"""Upstream-MXNet binary .params interop (reference: NDArray::Save/Load,
model.py load_checkpoint): byte-level round trips, legacy version reading,
and loading a whole zoo checkpoint into a net."""
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, upstream


def test_roundtrip_dict_and_list(tmp_path):
    f = str(tmp_path / "w.params")
    d = {"a": nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)),
         "b": nd.array(np.ones((4,), np.float16)),
         "c": nd.array(np.arange(5, dtype=np.int32))}
    upstream.save_params(f, d)
    out = upstream.load_params(f)
    assert set(out) == {"a", "b", "c"}
    for k in d:
        assert out[k].dtype == d[k].dtype
        np.testing.assert_array_equal(out[k].asnumpy(), d[k].asnumpy())
    # list form: no names block -> list comes back
    f2 = str(tmp_path / "l.params")
    upstream.save_params(f2, [d["a"], d["b"]])
    out2 = upstream.load_params(f2)
    assert isinstance(out2, list) and len(out2) == 2


def _legacy_file(path, version):
    """Hand-craft a one-array file in an older per-array layout."""
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = [struct.pack("<QQ", 0x112, 0), struct.pack("<Q", 1)]
    if version == "v3":
        out.append(struct.pack("<I", 0xF993FACA))
        out.append(struct.pack("<i", 0))                      # dense stype
        out.append(struct.pack("<I", 2))
        out += [struct.pack("<q", d) for d in arr.shape]
    elif version == "v1":
        out.append(struct.pack("<I", 0xF993FAC8))
        out.append(struct.pack("<I", 2))
        out += [struct.pack("<I", d) for d in arr.shape]
    else:  # pre-magic legacy: first u32 IS ndim
        out.append(struct.pack("<I", 2))
        out += [struct.pack("<I", d) for d in arr.shape]
    out.append(struct.pack("<ii", 1, 0))
    out.append(struct.pack("<i", 0))                          # float32
    out.append(arr.tobytes())
    out.append(struct.pack("<Q", 1))
    out.append(struct.pack("<Q", 1))
    out.append(b"w")
    open(path, "wb").write(b"".join(out))
    return arr


@pytest.mark.parametrize("version", ["v3", "v1", "legacy"])
def test_reads_all_ndarray_versions(tmp_path, version):
    f = str(tmp_path / f"{version}.params")
    arr = _legacy_file(f, version)
    out = upstream.load_params(f)
    np.testing.assert_array_equal(out["w"].asnumpy(), arr)


def test_sparse_stype_rejected(tmp_path):
    f = str(tmp_path / "s.params")
    out = [struct.pack("<QQ", 0x112, 0), struct.pack("<Q", 1),
           struct.pack("<I", 0xF993FACA), struct.pack("<i", 1)]  # row_sparse
    open(f, "wb").write(b"".join(out))
    with pytest.raises(mx.MXNetError, match="sparse"):
        upstream.load_params(f)


def test_checkpoint_arg_aux_split(tmp_path):
    f = str(tmp_path / "ck.params")
    upstream.save_params(f, {
        "arg:fc_weight": nd.ones((2, 2)),
        "aux:bn_moving_mean": nd.zeros((2,))})
    arg, aux = upstream.load_checkpoint_params(f)
    assert list(arg) == ["fc_weight"] and list(aux) == ["bn_moving_mean"]


def test_zoo_checkpoint_loads_identical_logits(tmp_path):
    """Acceptance: an upstream-format file written
    under a DIFFERENT scope prefix (as another process would produce)
    loads into resnet18_v1 and reproduces the exact logits of direct
    set_data."""
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    src = resnet18_v1(classes=10)
    src.initialize()
    x = nd.random.uniform(shape=(2, 3, 32, 32))
    ref = src(x).asnumpy()

    # simulate an upstream save: gluon-style bare names, different scope
    # counter (resnetv10_ -> resnetv17_), arg/aux split like a Module ckpt
    f = str(tmp_path / "resnet18-0000.params")
    blob = {}
    for k, v in src.collect_params().items():
        kind = "aux" if "running_" in k else "arg"
        blob[f"{kind}:{k.replace('resnetv10_', 'resnetv17_', 1)}"] = v.data()
    upstream.save_params(f, blob)

    dst = resnet18_v1(classes=10)
    dst.initialize()
    dst(x)  # materialise shapes
    assert not np.allclose(dst(x).asnumpy(), ref)
    loaded = upstream.load_params_into(dst, f)
    assert len(loaded) == len(src.collect_params())
    np.testing.assert_allclose(dst(x).asnumpy(), ref, rtol=1e-6)


def test_load_into_shape_mismatch_and_missing(tmp_path):
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=3)
    net.initialize()
    f = str(tmp_path / "bad.params")
    upstream.save_params(f, {"weight": nd.ones((5, 3))})
    with pytest.raises(mx.MXNetError, match="shape mismatch"):
        upstream.load_params_into(net, f, name_map={
            list(net.collect_params())[0]: "weight"})
    f2 = str(tmp_path / "other.params")
    upstream.save_params(f2, {"unrelated_tensor": nd.ones((2,))})
    with pytest.raises(mx.MXNetError, match="no parameter"):
        upstream.load_params_into(net, f2)
    assert upstream.load_params_into(net, f2, allow_missing=True,
                                     ignore_extra=True) == []


def test_bn_stats_match_across_scoping(tmp_path):
    """Scoped file into a bare-named net: running_mean/var (multi-segment
    suffixes) must match via scope-strip like gamma/beta do."""
    from mxnet_tpu.gluon import nn
    net = nn.BatchNorm(in_channels=3)
    net.initialize()
    x = nd.random.uniform(shape=(2, 3, 4, 4))
    net(x)
    ours = list(net.collect_params())
    f = str(tmp_path / "bn.params")
    vals = {k: nd.random.uniform(shape=v.shape)
            for k, v in net.collect_params().items()}
    # a scoped save, plus one extra tensor so positional fallback CANNOT
    # kick in — name matching alone must succeed
    blob = {f"model0_{k}": v for k, v in vals.items()}
    blob["model0_unrelated_extra0_weight"] = nd.ones((7,))
    upstream.save_params(f, blob)
    loaded = upstream.load_params_into(net, f, ignore_extra=True)
    assert sorted(loaded) == sorted(ours)
    for k in ours:
        np.testing.assert_allclose(
            net.collect_params()[k].data().asnumpy(), vals[k].asnumpy())


def test_positional_fallback_is_all_or_nothing(tmp_path):
    """A partially-matching file must not crash with a stale positional
    table (regression: KeyError when a name match consumed a key the
    positional table still referenced)."""
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=4), nn.Dense(4, in_units=4))
    net.initialize()
    ours = list(net.collect_params())
    # file where one DRIFTED key collides with a real param name (it
    # holds a different position's tensor) while the ordered shape+suffix
    # sequence still aligns -> the consistent positional bijection must
    # win over the stale name match, with no KeyError
    f = str(tmp_path / "mix.params")
    vals = [nd.random.uniform(shape=net.collect_params()[k].shape)
            for k in ours]
    keys = [ours[2], "drift0_bias", "drift1_weight", "drift1_bias"]
    upstream.save_params(f, dict(zip(keys, vals)))
    loaded = upstream.load_params_into(net, f)
    assert sorted(loaded) == sorted(ours)
    for k, v in zip(ours, vals):
        np.testing.assert_allclose(
            net.collect_params()[k].data().asnumpy(), v.asnumpy())


def test_unsupported_dtype_raises(tmp_path):
    with pytest.raises(mx.MXNetError, match="type_flag"):
        upstream.save_params(str(tmp_path / "b.params"),
                             {"m": np.zeros((2,), dtype=np.uint32)})


def test_load_params_malformed_raises_cleanly(tmp_path):
    """Truncated/garbage .params files raise MXNetError at every cut
    point — never a hang or a bare struct/Index error (same contract the
    ONNX decoder pins)."""
    from mxnet_tpu.upstream import save_params, load_params
    p = {"arg:w": nd.array(np.random.randn(4, 3).astype(np.float32)),
         "aux:m": nd.array(np.zeros(3, np.float32))}
    good = str(tmp_path / "u.params")
    save_params(good, p)
    raw = open(good, "rb").read()
    bad = str(tmp_path / "bad.params")
    for cut in (1, 8, len(raw) // 3, len(raw) // 2, len(raw) - 2):
        open(bad, "wb").write(raw[:cut])
        with pytest.raises(mx.base.MXNetError):
            load_params(bad)
    open(bad, "wb").write(b"\xff" * 64)
    with pytest.raises(mx.base.MXNetError):
        load_params(bad)
