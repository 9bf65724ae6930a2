"""AOT program assets (fishnet_tpu/aot/): fingerprint keying, the
fallback ladder, and pack/warm bundle integrity.

The fast tier drives the registry with tiny jit programs so the whole
file runs in seconds; one engine-level pack -> warm-boot round-trip is
marked slow (and tools/aot_smoke.py covers the same contract in CI
across real process boundaries, which is the part an in-process test
cannot prove).
"""
import hashlib
import json
import os
import pickle
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.aot import keys, pack, registry
from fishnet_tpu.utils import compile_cache


def _mul(x, y, scale=2):
    return jnp.sum(x * y) * scale


def _wrap_mul(name="mul"):
    return registry.wrap(
        name,
        jax.jit(_mul, static_argnames=("scale",)),
        _mul,
        static_names=("scale",),
    )


@pytest.fixture
def aot_root(tmp_path):
    """A store root, with the process-wide registry AND compile-cache
    state snapshotted/restored: installing an exporting registry
    force-disables the persistent XLA cache, and the rest of the suite
    depends on it (conftest enables it for compile-time reasons)."""
    prev_reg = registry.REGISTRY
    prev_forced = compile_cache._force_disabled
    prev_path = compile_cache._enabled_path
    yield str(tmp_path / "store")
    registry.REGISTRY = prev_reg
    compile_cache._force_disabled = prev_forced
    compile_cache._enabled_path = None
    if not prev_forced and prev_path is not None:
        # disable_compile_cache() leaves the directory alone and flips
        # jax's own switch; flip it back and re-enable
        jax.config.update("jax_enable_compilation_cache", True)
        restored = compile_cache.enable_compile_cache()
        assert restored == prev_path, (restored, prev_path)


def _export_tiny_bundle(root, warnings=None):
    """Export one tiny program into `root`; returns (store_dir, x, y, ref)."""
    reg = registry.install(root, export=True,
                           logger=(warnings.append if warnings is not None
                                   else None))
    prog = _wrap_mul()
    x = jnp.arange(8, dtype=jnp.float32)
    y = jnp.ones(8, dtype=jnp.float32)
    ref = np.asarray(prog(x, y, scale=3))
    reg.flush()
    reg.set_covers(["tiny"])
    assert reg.manifest["programs"], "export produced no artifact"
    return reg.dir, x, y, ref


# ------------------------------------------------------------ fingerprint


def test_fingerprint_roundtrip_and_digest_stability():
    fp = keys.store_fingerprint()
    assert fp["jax"] == jax.__version__
    assert keys.diff_fingerprints(fp, fp) == []
    # digest survives a JSON round-trip (manifests store the dict)
    again = json.loads(json.dumps(fp))
    assert keys.fingerprint_digest(again) == keys.fingerprint_digest(fp)


def test_fingerprint_skew_is_named_field_by_field():
    ours = keys.store_fingerprint()
    theirs = json.loads(json.dumps(ours))
    theirs["jaxlib"] = "0.0.1"
    theirs["settings"]["FISHNET_TPU_MAX_PLY"] = "99"
    diff = keys.diff_fingerprints(ours, theirs)
    assert any(d.startswith("jaxlib:") for d in diff)
    assert any(d.startswith("settings.FISHNET_TPU_MAX_PLY:") for d in diff)
    assert len(diff) == 2


def test_fingerprint_folds_mesh_topology():
    fp = keys.store_fingerprint()
    # topology fields present and coherent with the live process
    assert fp["mesh_axes"] == "dp"
    assert fp["process_count"] == jax.process_count()
    assert fp["mesh_shape"] == str(len(jax.devices()))


def test_topology_skewed_bundle_rejected_with_fields_named(aot_root):
    """A bundle packed on a different pod topology must be rejected with
    mesh_shape / process_count named — a sharded executable bakes its
    mesh in, and loading it cross-topology deserializes garbage."""
    theirs = json.loads(json.dumps(keys.store_fingerprint()))
    theirs["mesh_shape"] = "16x2"
    theirs["process_count"] = 4
    diff = keys.diff_fingerprints(keys.store_fingerprint(), theirs)
    assert any(d.startswith("mesh_shape:") for d in diff)
    assert any(d.startswith("process_count:") for d in diff)
    assert len(diff) == 2

    other = os.path.join(aot_root, keys.fingerprint_digest(theirs)[:12])
    os.makedirs(other)
    with open(os.path.join(other, "manifest.json"), "w") as f:
        json.dump({"version": registry.MANIFEST_VERSION,
                   "fingerprint": theirs, "programs": {"k": {}},
                   "covers": []}, f)
    warnings = []
    reg = registry.install(aot_root, logger=warnings.append)
    assert not reg.active
    assert any("incompatible" in w and "mesh_shape" in w
               and "process_count" in w for w in warnings)


def test_program_key_canonicalizes_statics_and_avals():
    x = jnp.arange(4, dtype=jnp.float32)
    k1, meta = keys.program_key("p", {"s": 1}, None, (x,))
    k2, _ = keys.program_key("p", {"s": 1}, None, (x + 1,))  # same aval
    assert k1 == k2
    k3, _ = keys.program_key("p", {"s": 2}, None, (x,))      # static skew
    k4, _ = keys.program_key(
        "p", {"s": 1}, None, (jnp.arange(5, dtype=jnp.float32),)
    )                                                        # shape skew
    assert len({k1, k3, k4}) == 3
    assert meta["entry"] == "p"


def test_incompatible_sibling_store_rejected_with_reason(aot_root):
    # a sibling fingerprint dir (e.g. packed under another jaxlib) must
    # produce an explicit rejection line, not a silent cold boot
    theirs = json.loads(json.dumps(keys.store_fingerprint()))
    theirs["jaxlib"] = "0.0.1"
    other = os.path.join(aot_root, keys.fingerprint_digest(theirs)[:12])
    os.makedirs(other)
    with open(os.path.join(other, "manifest.json"), "w") as f:
        json.dump({"version": registry.MANIFEST_VERSION,
                   "fingerprint": theirs, "programs": {"k": {}},
                   "covers": []}, f)
    warnings = []
    reg = registry.install(aot_root, logger=warnings.append)
    assert not reg.active
    assert any("incompatible" in w and "jaxlib" in w for w in warnings)


# --------------------------------------------------------- fallback ladder


def test_export_load_bit_identity_and_positional_statics(aot_root):
    _, x, y, ref = _export_tiny_bundle(aot_root)

    # fresh read-only registry + fresh wrapper (empty in-memory cache):
    # the call must come from a DISK load, and answer bit-identically
    reg = registry.install(aot_root)
    assert reg.active
    prog = _wrap_mul()
    out = np.asarray(prog(x, y, scale=3))
    assert reg.stats["loads"] == 1 and reg.stats["misses"] == 0
    np.testing.assert_array_equal(out, ref)

    # keyword vs positional static canonicalize to the same program
    out2 = np.asarray(prog(x, y, 3))
    assert reg.stats["loads"] == 1 and reg.stats["misses"] == 0
    assert reg.stats["hits"] == 2
    np.testing.assert_array_equal(out2, ref)


def test_miss_degrades_to_jit_with_one_warning(aot_root):
    _export_tiny_bundle(aot_root)
    warnings = []
    reg = registry.install(aot_root, logger=warnings.append)
    prog = _wrap_mul()
    x = jnp.arange(16, dtype=jnp.float32)  # shape the bundle never saw
    y = jnp.ones(16, dtype=jnp.float32)
    out = np.asarray(prog(x, y, scale=3))
    np.testing.assert_array_equal(out, np.asarray(_mul(x, y, 3)))
    assert reg.stats["misses"] == 1 and reg.stats["errors"] == 0
    assert sum("miss" in w for w in warnings) == 1
    # second call takes the cached-miss short-circuit: no new warning,
    # no second disk probe, and the count stays put
    np.asarray(prog(x, y, scale=3))
    assert reg.stats["misses"] == 1
    assert sum("miss" in w for w in warnings) == 1


def test_corrupted_artifact_quarantined_not_fatal(aot_root):
    store_dir, x, y, ref = _export_tiny_bundle(aot_root)
    blob_dir = os.path.join(store_dir, "blobs")
    (name,) = os.listdir(blob_dir)
    path = os.path.join(blob_dir, name)
    with open(path, "wb") as f:
        f.write(b"garbage")

    warnings = []
    reg = registry.install(aot_root, logger=warnings.append)
    prog = _wrap_mul()
    out = np.asarray(prog(x, y, scale=3))  # must not raise
    np.testing.assert_array_equal(out, ref)
    assert reg.stats["errors"] == 1 and reg.stats["loads"] == 0
    assert os.path.isfile(path + ".bad") and not os.path.isfile(path)
    assert any("quarantined" in w for w in warnings)


def test_undeserializable_artifact_quarantined(aot_root):
    # blob whose sha MATCHES its manifest entry but whose payload is not
    # a serialized executable: the deserialize step itself must
    # quarantine and fall back, covering the post-sha rung of the ladder
    store_dir, x, y, ref = _export_tiny_bundle(aot_root)
    blob_dir = os.path.join(store_dir, "blobs")
    (name,) = os.listdir(blob_dir)
    path = os.path.join(blob_dir, name)
    bogus = zlib.compress(pickle.dumps((b"not-an-executable", None, None, [0])))
    with open(path, "wb") as f:
        f.write(bogus)
    man_path = os.path.join(store_dir, "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    key = name[: -len(".bin")]
    man["programs"][key]["sha256"] = hashlib.sha256(bogus).hexdigest()
    with open(man_path, "w") as f:
        json.dump(man, f)

    reg = registry.install(aot_root)
    prog = _wrap_mul()
    out = np.asarray(prog(x, y, scale=3))
    np.testing.assert_array_equal(out, ref)
    assert reg.stats["errors"] == 1
    assert os.path.isfile(path + ".bad")


def test_star_args_signature_stays_plain_jit(aot_root):
    _export_tiny_bundle(aot_root)
    reg = registry.install(aot_root)

    def varargs(*xs):
        return sum(xs)

    prog = registry.wrap("varargs", jax.jit(varargs), varargs)
    assert np.asarray(prog(jnp.ones(2), jnp.ones(2))).tolist() == [2.0, 2.0]
    assert reg.stats == {"hits": 0, "misses": 0, "loads": 0,
                         "errors": 0, "exports": 0}


def test_warm_covers_semantics(aot_root):
    warnings = []
    # exporting registry never reports covered (pack IS the warmup)
    reg = registry.install(aot_root, export=True, logger=warnings.append)
    prog = _wrap_mul()
    prog(jnp.ones(4), jnp.ones(4), scale=2)
    reg.flush()
    reg.set_covers(["tiny"])
    assert not registry.warm_covers("tiny")

    registry.install(aot_root)
    assert registry.warm_covers("tiny")
    assert not registry.warm_covers("tiny", "variants")
    assert registry.boot_report()["enabled"]

    # an empty read-only store covers nothing and deactivates
    registry.install(os.path.join(aot_root, "empty"))
    assert not registry.warm_covers("tiny")
    assert not registry.boot_report()["enabled"]


# ------------------------------------------------------------- pack / warm


def test_pack_warm_load_manifest_integrity(aot_root):
    store_dir, x, y, ref = _export_tiny_bundle(aot_root)

    man = pack.verify_bundle(store_dir)
    assert man["covers"] == ["tiny"] and man["programs"]

    # warm into a second root: accepts the store ROOT (resolves the
    # nested fingerprint dir), re-verifies, and copies everything
    dest_root = os.path.join(os.path.dirname(aot_root), "live")
    rep = pack.warm(aot_root, dest_root, logger=lambda m: None)
    assert rep["programs"] == len(man["programs"])
    installed = pack.verify_bundle(rep["dir"])
    assert installed["programs"].keys() == man["programs"].keys()

    # the warmed copy serves a real load
    reg = registry.install(dest_root)
    out = np.asarray(_wrap_mul()(x, y, scale=3))
    np.testing.assert_array_equal(out, ref)
    assert reg.stats["loads"] == 1

    # verify names a corrupted artifact
    blob_dir = os.path.join(rep["dir"], "blobs")
    (name,) = os.listdir(blob_dir)
    with open(os.path.join(blob_dir, name), "ab") as f:
        f.write(b"x")
    with pytest.raises(ValueError, match="sha256"):
        pack.verify_bundle(rep["dir"])


def test_warm_rejects_fingerprint_skew(aot_root, tmp_path):
    store_dir, *_ = _export_tiny_bundle(aot_root)
    man_path = os.path.join(store_dir, "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    man["fingerprint"]["jaxlib"] = "0.0.1"
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ValueError, match="jaxlib"):
        pack.warm(store_dir, str(tmp_path / "dest"), logger=lambda m: None)


# ------------------------------------------------------------ engine level


@pytest.mark.slow
def test_engine_pack_then_warm_boot_bit_identity(aot_root):
    """pack() over a real TpuEngine, then a warm in-process boot: warmup
    reports itself skipped, the first dispatch loads from disk, and the
    scores match a plain-JIT engine bit for bit."""
    from fishnet_tpu.chess.position import Position
    from fishnet_tpu.engine.tpu import TpuEngine
    from fishnet_tpu.ops import search as search_ops
    from fishnet_tpu.ops.board import from_position, stack_boards

    def run_search(eng):
        roots = stack_boards([from_position(Position.initial())] * 16)
        out = eng._search(
            roots, np.ones(16, np.int32), np.full(16, 64, np.int32)
        )
        return (np.asarray(out["score"]).tolist(),
                int(np.asarray(out["nodes"]).sum()))

    progs = (search_ops._run_segment_jit, search_ops._init_state_jit,
             search_ops._splice_lanes_jit)
    registry.uninstall()
    ref = run_search(TpuEngine())

    rep = pack.pack(aot_root, logger=lambda m: None)
    assert rep["programs"] > 0 and "buckets" in rep["covers"]

    # fresh-process simulation: drop the in-memory executables the pack
    # left behind so the warm boot must load from the store
    for p in progs:
        p.cache.clear()
    logs = []
    registry.install(aot_root, logger=logs.append)
    eng = TpuEngine()
    covered = eng.warmup(None, logs.append)
    assert "buckets" in covered
    assert any("skipped" in m and "AOT" in m for m in logs)
    warm = run_search(eng)
    reg = registry.REGISTRY
    assert reg.stats["loads"] >= 1 and reg.stats["misses"] == 0
    assert reg.stats["errors"] == 0
    assert warm == ref
