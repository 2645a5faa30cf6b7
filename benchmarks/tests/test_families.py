"""The seam for architectures: the ``llama`` family is what the harness held
before (same bits, same numbers), trees of any depth are wrapped, every counter
and every operation's time reaches the readers, and only ``sut.py`` imports
the program.  CPU, tiny sizes."""
import hashlib
import json
import os
import re

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCHMARKS)

# sha256 over the sorted (key path, dtype, shape, bytes) of every leaf, taken on the tree before the
# families existed (commit 867adc2): what ``weights.stacked`` drew, and what ``write_checkpoint`` saved
PARENT_DIGESTS = {
    21: ("4f803f0c2607105892db38871c26181e45a293ed01f6a7bcbe4e8707d23c0f8f",
         "9021bcf7fcba230dfe80cc8898f4c4cc32aeec87cdb4b1087f244df928c25b61"),
    2**31 + 9: ("704291b5285beb6f5ced494c35c3ee12daaf6ae9313a70f78cb5c3a3dbc8e291",
                "a2bccba523afa4e2c43febac02136fa9e22629ccd40020275fc89d370f6ecfb4"),
}


def _conf():
    with open(os.path.join(BENCHMARKS, "configs", "tiny-rehearsal.json")) as f:
        return json.load(f)


def _llama():
    from benchmarks import families

    return families.load(_conf(), BENCHMARKS)


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    leaves = sorted(((jax.tree_util.keystr(p), l) for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]),
                    key=lambda kv: kv[0])
    for path, leaf in leaves:
        a = np.asarray(leaf)
        h.update(f"{path}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_the_llama_family_draws_the_parents_tree_bit_for_bit(seed):
    import jax
    import jax.numpy as jnp

    from benchmarks import sut, weights

    conf, raw_digest, saved_digest = _conf(), *PARENT_DIGESTS[seed]
    w = weights.stacked(conf["hf"], seed, conf["weights"]["head_ids"])
    assert _digest(w) == raw_digest  # weights.py itself has not moved
    tree = _llama().served_params(conf, seed)
    want = {"layers": w["layers"], **w["top"]}
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape and np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # and what the child hands to save_model is what the parent's write_checkpoint handed it
    assert _digest(sut.wrap_params(tree, jnp.float32)) == saved_digest


@pytest.mark.parametrize("control", [None, "int4", "kv_fp8"])
def test_reference_logits_is_logits_at_as_correct_py_called_it(control):
    from benchmarks import weights
    from benchmarks.reference import decoder

    conf, seed = _conf(), 5
    hf, (lo, hi) = conf["hf"], conf["weights"]["head_ids"]
    cols = list(range(lo, hi + 1))
    rng = np.random.default_rng(1)
    seqs = [list(map(int, rng.integers(lo, hi + 1, n))) for n in (40, 23)]
    firsts = [17, 9]
    top = weights.dequantised_top(hf, seed, (lo, hi))
    int4_group, kv_round = {None: (0, None), "int4": (64, None), "kv_fp8": (0, "float8_e4m3fn")}[control]
    want = decoder.logits_at(hf, lambda i: weights.dequantised_layer(hf, seed, i, int4_group), top,
                             seqs, firsts, kv_round=kv_round, columns=cols)
    family = _llama()
    assert set(family.CONTROLS) == {"int4", "kv_fp8"}
    got = family.reference_logits(conf, seed, seqs, firsts, cols, control=control)
    assert [g.shape for g in got] == [(22, 95), (13, 95)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    if control:  # a control is another computation, not the reference again
        plain = family.reference_logits(conf, seed, seqs, firsts, cols)
        assert max(float(np.abs(g - p).max()) for g, p in zip(got, plain)) > 1e-3


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        _llama().reference_logits(_conf(), 1, [[40, 41, 42]], [0], [40, 41], control="int2")


def test_a_tree_nested_two_levels_deep_is_wrapped_at_every_depth():
    import jax.numpy as jnp

    from benchmarks import sut
    from django_assistant_bot_tpu.ops.quant import QTensor

    q, scale = jnp.ones((2, 4, 3), jnp.int8), jnp.full((2, 1, 3), 0.5, jnp.float32)
    norm = jnp.ones((2, 4), jnp.bfloat16)
    tree = {"tok_embed": norm, "kinds": {"window": {"wq": (q, scale), "sink": norm, "experts": {"w_up": (q, scale)}},
                                         "global": [{"wq": (q, scale)}, {"norm": norm}]}}
    out = sut.wrap_params(tree, jnp.float32)
    quantised = [out["kinds"]["window"]["wq"], out["kinds"]["window"]["experts"]["w_up"], out["kinds"]["global"][0]["wq"]]
    assert all(type(t) is QTensor and t.q.dtype == jnp.int8 and t.scale.dtype == jnp.float32 for t in quantised)
    plain = [out["tok_embed"], out["kinds"]["window"]["sink"], out["kinds"]["global"][1]["norm"]]
    assert all(t.dtype == jnp.float32 and t.shape == (2, 4) for t in plain)


class _StubEngine:
    """What ``counters`` asks of an engine: three public calls."""

    class scheduler:
        @staticmethod
        def wait_stats():
            return {"interactive": {"n": 3, "p95_ms": 12.5}, "batch": {"n": 0, "p95_ms": float("nan")}}

    def tick_stats(self):
        return {"ticks": 40, "issue_ms": 1.5, "block_ms": 80.0, "decode_steps": 8, "prefill_chunks_piggybacked": 2,
                "decode_kv_path": "xla", "prefill_tokens_real": np.int64(900), "prefill_tokens_padded": 1536,
                "loop": {"tick_block": {"s": np.float32(3.25), "n": 40}, "idle_wait": {"s": 0.0, "n": 0}},
                "kv": self.kv_stats(), "supervision": {"engine_restarts": 1, "poisoned_requests": 0},
                "a_lock": object(), "buckets": (128, 256), "bad": float("inf")}

    def kv_stats(self):
        return {"prefix_hits": 7, "prefix_misses": 5, "kv_pages_total": 32, "kv_pages_used": 9, "kv_evictions": 4,
                "pages_freed_behind_window": 11}


def test_counters_keep_their_twelve_keys_and_carry_the_whole_dictionaries_json_safe():
    from benchmarks import sut

    out = sut.counters(_StubEngine())
    assert {k: out[k] for k in ("ticks", "tick_issue_total_ms", "tick_block_ms_avg", "decode_steps",
                                "prefill_chunks_piggybacked", "prefix_hits", "prefix_misses", "kv_pages_total",
                                "kv_pages_used", "kv_evictions", "engine_restarts", "poisoned_requests")} == {
        "ticks": 40, "tick_issue_total_ms": 60.0, "tick_block_ms_avg": 80.0, "decode_steps": 8,
        "prefill_chunks_piggybacked": 2, "prefix_hits": 7, "prefix_misses": 5, "kv_pages_total": 32,
        "kv_pages_used": 9, "kv_evictions": 4, "engine_restarts": 1, "poisoned_requests": 0}
    assert out["sched_wait_p95_ms"] == 12.5
    assert json.loads(json.dumps(out, allow_nan=False)) == out  # survives the pipe to run.py as it is
    ts = out["tick_stats"]
    assert ts["loop"]["tick_block"] == {"s": 3.25, "n": 40} and ts["decode_kv_path"] == "xla"
    assert ts["prefill_tokens_real"] == 900 and ts["buckets"] == [128, 256]
    assert "a_lock" not in ts and "bad" not in ts  # what JSON cannot carry is dropped, not guessed
    assert out["kv_stats"]["pages_freed_behind_window"] == 11  # a counter this harness has never heard of
    assert out["wait_stats"]["batch"] == {"n": 0}


def test_trace_reduce_keeps_its_numbers_and_adds_every_operation_and_scope():
    from benchmarks import trace_reduce

    r = trace_reduce.reduce(os.path.join(BENCHMARKS, "fixtures", "tpu_small.xplane.pb"))
    # as the parent tree read this file
    assert r["device_ops"] == [["convolution_reduce_fusion", pytest.approx(0.0005406540000000015, rel=1e-12)],
                               ["copy-start", pytest.approx(7.900000000005125e-08, rel=1e-12)],
                               ["copy-done", pytest.approx(1.2999999993157374e-08, rel=1e-12)]]
    assert r["busy_s"] == pytest.approx(0.0005407459999999947, rel=1e-12)
    assert r["program_s"] == {"jit__lambda": pytest.approx(0.000540785000000002, rel=1e-12)}
    assert r["program_runs"] == {"jit__lambda": 6.0} and r["marked"] and r["devices"] == 1
    top_ten = sorted(r["op_s"].items(), key=lambda kv: -kv[1])[:10]
    assert [list(kv) for kv in top_ten] == r["device_ops"]
    assert r["scope_s"]["jit(<lambda>)/dot_general:"] == r["op_s"]["convolution_reduce_fusion"]
    assert set(r["scope_s"]) == {"", "jit(<lambda>)/dot_general:"}  # the copies carry no scope
    assert sum(r["scope_s"].values()) == pytest.approx(sum(r["op_s"].values()), rel=1e-12)


def test_without_xplane_pb2_the_scopes_are_none_and_the_rest_stands(monkeypatch):
    from benchmarks import trace_reduce

    monkeypatch.setattr(trace_reduce, "_xplane_pb2", lambda: None)
    r = trace_reduce.reduce(os.path.join(BENCHMARKS, "fixtures", "tpu_small.xplane.pb"))
    assert r["scope_s"] is None and len(r["op_s"]) == 3 and r["busy_s"] > 0


def test_a_family_that_has_no_file_ends_the_run_before_any_child_is_started(tmp_path, monkeypatch):
    from benchmarks import run

    data = tmp_path / "benchmarks"
    (data / "configs").mkdir(parents=True)
    (data / "traffic").mkdir()
    (data / "families").mkdir()
    conf = dict(_conf(), name="tiny-elsewhere", family="not-written-yet")
    (data / "configs" / "tiny-elsewhere.json").write_text(json.dumps(conf))
    with open(os.path.join(BENCHMARKS, "traffic", "tiny-open.json")) as f:
        (data / "traffic" / "tiny-open.json").write_text(f.read())
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-elsewhere", "source": "none", "why": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-elsewhere.json"})
    bench["workloads"].append({"name": "elsewhere.open", "config": "tiny-elsewhere", "traffic": "tiny-open",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "Child", lambda *a, **k: pytest.fail("a child was started"))
    with pytest.raises(SystemExit) as e:
        run.main(["--benchmark-json", str(tmp_path / "BENCHMARK.json"), "--data-root", str(tmp_path),
                  "--workload", "elsewhere.open", "--seed", "1", "--seconds", "2"])
    assert str(data / "families" / "not-written-yet.py") in str(e.value)


def test_a_family_module_without_its_interface_is_refused(tmp_path):
    from benchmarks import families

    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text("LIMITS = {}\nCONTROLS = ()\n")
    with pytest.raises(SystemExit, match="served_params"):
        families.load({"name": "c", "family": "half"}, str(tmp_path))


def test_only_sut_py_imports_the_program():
    named = []
    for d, _, files in os.walk(BENCHMARKS):
        if os.path.relpath(d, BENCHMARKS).split(os.sep)[0] in ("tests", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"^\s*(from|import)\s+django_assistant_bot_tpu", fh.read(), re.M):
                        named.append(os.path.relpath(os.path.join(d, f), BENCHMARKS))
    assert named == ["sut.py"]
    assert os.path.isfile(os.path.join(BENCHMARKS, "families", "llama.py"))  # and the walk did reach the families


def test_a_family_that_imports_jax_as_it_is_loaded_is_refused_by_run_py(tmp_path):
    """``run.py`` never touches JAX.  In processes of their own, because this one already has."""
    import subprocess
    import sys

    (tmp_path / "families").mkdir()
    with open(os.path.join(BENCHMARKS, "families", "llama.py")) as f:
        lazy = f.read()
    (tmp_path / "families" / "lazy.py").write_text(lazy)
    (tmp_path / "families" / "eager.py").write_text(lazy + "\nimport jax\n")

    def load(name):
        code = (f"import sys; sys.path.insert(0, {ROOT!r})\nfrom benchmarks import run\n"
                f"run.load_family({{'name': 'c', 'family': {name!r}}}, {str(tmp_path)!r})")
        return subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))

    done = load("lazy")
    assert done.returncode == 0, done.stderr
    done = load("eager")
    assert done.returncode != 0 and "imports JAX as it is loaded" in done.stderr
