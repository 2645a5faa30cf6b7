"""CLI smoke tests: load_csv, search, emb_test, queue — each command's run()
drives the real stack (reference: the management commands in SURVEY §2.1 #21)."""

import argparse

import numpy as np
import pytest

from django_assistant_bot_tpu.cli import emb_test, load_csv, queue_cmd, search
from django_assistant_bot_tpu.conf import settings
from django_assistant_bot_tpu.rag.index_registry import reset_indexes
from django_assistant_bot_tpu.storage import models


@pytest.fixture(autouse=True)
def fresh_indexes():
    reset_indexes()
    yield
    reset_indexes()


@pytest.fixture()
def csv_loaded(tmp_db, tmp_path, capsys):
    path = tmp_path / "docs.csv"
    path.write_text(
        "topic,title,content\n"
        "Billing,Refunds,Refunds take three days.\n"
        "Billing,Invoices,Invoices are emailed monthly.\n"
        "Access,Login,Reset your password from the login page.\n"
    )
    args = argparse.Namespace(bot_codename="clibot", path=str(path), no_process=True)
    assert load_csv.run(args) == 0
    assert "Loaded 3 documents" in capsys.readouterr().out
    return models.Bot.objects.get(codename="clibot")


def test_load_csv_builds_wiki_tree(csv_loaded):
    bot = csv_loaded
    docs = models.WikiDocument.objects.filter(bot=bot).all()
    titles = {d.title for d in docs}
    assert {"Billing", "Access", "Refunds", "Invoices", "Login"} <= titles
    refunds = next(d for d in docs if d.title == "Refunds")
    assert refunds.parent_id is not None  # 2-level topic tree


def test_search_cli_finds_ingested_question(csv_loaded, capsys):
    bot = csv_loaded
    wiki = models.WikiDocument.objects.filter(bot=bot, title="Refunds").first()
    doc = models.Document.objects.create(wiki=wiki, name="Refunds")
    # embed via the SAME factory the search CLI uses, so dims always agree
    from django_assistant_bot_tpu.ai.services.ai_service import get_ai_embedder

    import asyncio

    emb = get_ai_embedder("test")
    vec = asyncio.run(emb.embeddings(["how long do refunds take?"]))[0]
    models.Question.objects.create(
        document=doc, text="how long do refunds take?", embedding=np.asarray(vec, np.float32)
    )
    with settings.override(EMBEDDING_AI_MODEL="test"):
        # a document only scores once it has >= max_scores_n hits (reference
        # aggregation semantics); one question in the corpus -> max_scores_n=1
        args = argparse.Namespace(
            query="how long do refunds take?", field="questions", max_scores_n=1, n=5
        )
        assert search.run(args) == 0
    out = capsys.readouterr().out
    assert "Refunds" in out  # the matching document is printed with its score


def test_emb_test_cli_prints_similarity(tmp_db, capsys):
    with settings.override(EMBEDDING_AI_MODEL="test"):
        args = argparse.Namespace(query1="hello", query2="hello", model=None)
        assert emb_test.run(args) == 0
    out = capsys.readouterr().out
    assert "Score: " in out
    score = float(out.split("Score:")[1].strip())
    assert score == pytest.approx(1.0, abs=1e-5)  # identical texts


def test_queue_cli_list_clear_remove(tmp_db, capsys):
    from django_assistant_bot_tpu.tasks.queue import TaskRecord

    for i in range(3):
        TaskRecord.objects.create(queue="query", name=f"tests.task{i}", args=[], kwargs={})
    assert queue_cmd.run(argparse.Namespace(action="list", queue=None, id=None, status=None)) == 0
    out = capsys.readouterr().out
    assert "tests.task0" in out and "tests.task2" in out

    first = TaskRecord.objects.all().order_by("id").first()
    assert (
        queue_cmd.run(argparse.Namespace(action="remove", queue=None, id=first.id, status=None))
        == 0
    )
    assert TaskRecord.objects.count() == 2
    assert queue_cmd.run(argparse.Namespace(action="clear", queue="query", id=None, status=None)) == 0
    assert TaskRecord.objects.count() == 0
    # remove without --id is a usage error
    assert queue_cmd.run(argparse.Namespace(action="remove", queue=None, id=None, status=None)) == 1


def test_fetch_models_skips_complete_and_reports_missing(tmp_path, capsys, monkeypatch):
    """fetch: an already-complete checkpoint dir is skipped (the reference's
    local_files_only probe, gpu_service/bin/fetch_models.py:10-30); an
    incomplete one without the hub client exits with guidance."""
    from django_assistant_bot_tpu.cli import fetch_models as fm

    models_dir = tmp_path / "models"
    done = models_dir / "org__done"
    done.mkdir(parents=True)
    (done / "config.json").write_text("{}")
    (done / "model.safetensors").write_text("x")
    assert fm.fetch_one("org/done", str(models_dir)) == str(done)
    assert "already fetched" in capsys.readouterr().out

    # force the no-hub-client path deterministically
    import builtins

    real_import = builtins.__import__

    def no_hub(name, *a, **k):
        if name == "huggingface_hub":
            raise ImportError("no hub in test")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_hub)
    with pytest.raises(SystemExit, match="manually"):
        fm.fetch_one("org/missing", str(models_dir))


def test_fetch_models_config_repo_ids(tmp_path):
    import json

    from django_assistant_bot_tpu.cli import fetch_models as fm

    cfg = tmp_path / "serving.json"
    local_dir = tmp_path / "local_ckpt"
    local_dir.mkdir()
    cfg.write_text(json.dumps({
        "chat": {"kind": "decoder", "path": "meta-llama/Llama-3.2-1B"},
        "tiny": {"kind": "decoder", "tiny": True},
        "local": {"kind": "decoder", "path": str(local_dir)},
    }))
    assert fm._config_repo_ids(str(cfg)) == ["meta-llama/Llama-3.2-1B"]


def test_fetch_models_config_skips_filesystem_paths(tmp_path, monkeypatch):
    """Filesystem-looking specs must never reach snapshot_download (r4 advisor:
    a not-yet-created local path like models/foo.native aborted the run)."""
    import json

    from django_assistant_bot_tpu.cli import fetch_models as fm

    monkeypatch.chdir(tmp_path)
    (tmp_path / "models").mkdir()
    cfg = tmp_path / "serving.json"
    cfg.write_text(json.dumps({
        "hub": {"kind": "decoder", "path": "org/real-repo"},
        "native": {"kind": "decoder", "path": "models/foo.native"},
        "native8": {"kind": "decoder", "path": "other/foo.native.int8"},
        "dot": {"kind": "decoder", "path": "./ckpt/dir"},
        "abs": {"kind": "decoder", "path": str(tmp_path / "nope")},
        "deep": {"kind": "decoder", "path": "a/b/c"},
    }))
    assert fm._config_repo_ids(str(cfg)) == ["org/real-repo"]


def test_fetch_models_continues_past_failures(tmp_path, monkeypatch, capsys):
    """One model failing must not abort the rest of the fetch run."""
    from types import SimpleNamespace

    from django_assistant_bot_tpu.cli import fetch_models as fm

    calls = []

    def fake_fetch(repo_id, models_dir, revision=None):
        calls.append(repo_id)
        if repo_id == "org/bad":
            raise SystemExit(f"{repo_id}: download failed")
        d = tmp_path / repo_id.replace("/", "__")
        d.mkdir(exist_ok=True)
        return str(d)

    monkeypatch.setattr(fm, "fetch_one", fake_fetch)
    args = SimpleNamespace(
        models=["org/bad", "org/good"], config=None, models_dir=str(tmp_path),
        revision=None, convert=False, kind="decoder", quantize=None,
    )
    rc = fm.run(args)
    assert calls == ["org/bad", "org/good"]  # kept going past the failure
    assert rc == 1  # but the run still reports it


def test_fetch_models_hub_id_not_swallowed_by_local_dir(tmp_path, monkeypatch, capsys):
    """A `google/` directory in CWD must not silently drop `google/gemma-2b`
    (ADVICE r5): only an EXISTING full path (or a .native convert target) is a
    local marker; the ambiguous case is logged and treated as a hub id."""
    from django_assistant_bot_tpu.cli import fetch_models as fm

    monkeypatch.chdir(tmp_path)
    (tmp_path / "google").mkdir()
    assert fm.looks_like_repo_id("google/gemma-2b")
    assert "treating it as a hub id" in capsys.readouterr().out
    # a not-yet-created converted checkpoint under an existing dir stays local
    (tmp_path / "models").mkdir()
    assert not fm.looks_like_repo_id("models/foo.native")
    # an existing full path stays local (no note)
    (tmp_path / "google" / "ckpt").mkdir()
    assert not fm.looks_like_repo_id("google/ckpt")
    assert "treating it as a hub id" not in capsys.readouterr().out


_CACHE_PROBE = """
import json, os, sys
from django_assistant_bot_tpu.utils import compile_cache as cc
first, second = cc.enable_persistent_compile_cache(), cc.enable_persistent_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: x * 2 + 1)(jnp.ones((4,))).block_until_ready()
print(json.dumps({"first": first, "second": second,
                  "config": jax.config.jax_compilation_cache_dir,
                  "entries": len(os.listdir(first))}))
"""


def _cache_probe(env_dir):
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "DABT_COMPILE_CACHE_OFF")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_jax_env_var(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, the function
    returns that path, and the program's compiles land there."""
    target = str(tmp_path / "from-env")
    got = _cache_probe(target)
    assert got["first"] == got["second"] == got["config"] == target
    assert got["entries"] >= 1


def test_compile_cache_defaults_to_one_fixed_in_checkout_path():
    """Unset: <checkout>/.cache/xla — the same in every call and every
    process, never ~, a temporary name, a pid or a time."""
    import os

    from django_assistant_bot_tpu.utils import compile_cache as cc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.IN_CHECKOUT_DIR == os.path.join(root, ".cache", "xla")
    a, b = _cache_probe(None), _cache_probe(None)
    for got in (a, b):
        assert got["first"] == got["second"] == got["config"] == cc.IN_CHECKOUT_DIR
        assert got["entries"] >= 1


def test_compile_cache_sets_no_directory_in_code_when_env_is_set(tmp_path, monkeypatch):
    import jax

    from django_assistant_bot_tpu.utils import compile_cache as cc

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: (updates.append(k), real_update(k, v))[1]
    )
    monkeypatch.setenv(cc.ENV_JAX_DIR, str(tmp_path / "elsewhere"))
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert cc.enable_persistent_compile_cache() == str(tmp_path / "elsewhere")
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / "elsewhere").exists()  # JAX creates it on first write
        monkeypatch.setenv(cc.ENV_DISABLE, "1")
        assert cc.enable_persistent_compile_cache() is None
    finally:
        real_update("jax_persistent_cache_min_compile_time_secs", prev)


def test_compile_cache_has_one_way_to_be_placed():
    """No second spelling of the directory, no temporary directories."""
    import inspect

    from django_assistant_bot_tpu.utils import compile_cache as cc

    src = inspect.getsource(cc)
    assert not hasattr(cc, "ENV_DIR") and "DABT_COMPILE_CACHE_DIR" not in src
    assert "expanduser" not in src and "mkdtemp" not in src and "getpid" not in src
    assert not inspect.signature(cc.enable_persistent_compile_cache).parameters


def test_second_process_on_a_held_chip_gets_one_clear_error(monkeypatch, capsys):
    """What a one-chip host's second JAX process really raises (observed on a
    v5e while `serve` held the chip) becomes one message naming the cause and
    what fits, exit code 3 — not libtpu's traceback about a lockfile."""
    from django_assistant_bot_tpu.cli import search
    from django_assistant_bot_tpu.cli.main import main

    held = (
        "Unable to initialize backend 'tpu': ABORTED: Internal error when accessing "
        'libtpu multi-process lockfile. Run "$ sudo rm /tmp/libtpu_lockfile". '
        "(set JAX_PLATFORMS='' to automatically choose an available backend)"
    )

    def taken(args):
        raise RuntimeError(held)

    monkeypatch.setattr(search, "run", taken)
    assert main(["search", "hello"]) == 3
    err = capsys.readouterr().err
    assert err.startswith("search: JAX could not initialise its backend")
    assert "ONE process at a time" in err and "JAX_PLATFORMS=cpu" in err
    assert "tpu:" in err and "gpu_service:" in err

    # any other RuntimeError is not ours to explain
    monkeypatch.setattr(search, "run", lambda args: (_ for _ in ()).throw(RuntimeError("boom")))
    import pytest

    with pytest.raises(RuntimeError, match="boom"):
        main(["search", "hello"])


def test_compile_cache_can_be_turned_off_for_the_process_and_slicing_leaves_it_on_off_the_tpu():
    """`disable_persistent_compile_cache` stops reads and writes for the rest
    of the process (what `MeshPlanner` does for multi-chip TPU slices, whose
    cached executables halt at launch); CPU planners never touch it."""
    import jax

    from django_assistant_bot_tpu.parallel import MeshPlanner
    from django_assistant_bot_tpu.utils import compile_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        MeshPlanner(2, devices=jax.devices()[:4])  # two 2-device CPU slices
        assert jax.config.jax_enable_compilation_cache is True
        cc.disable_persistent_compile_cache("test")
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_serve_knows_page_options_and_no_layout_option(capsys):
    """``serve`` names the page pool's size and page (``--kv-pages``,
    ``--kv-page-size``); ``--kv-layout`` went with the contiguous cache and is
    a usage error, not a silently ignored flag."""
    from django_assistant_bot_tpu.cli import serve

    parser = argparse.ArgumentParser()
    serve.add_parser(parser.add_subparsers(dest="command"))
    args = parser.parse_args(["serve", "--tiny", "--kv-pages", "12", "--kv-page-size", "32"])
    assert (args.kv_pages, args.kv_page_size) == (12, 32)
    with pytest.raises(SystemExit) as e:
        parser.parse_args(["serve", "--tiny", "--kv-layout", "legacy"])
    assert e.value.code == 2
    assert "--kv-layout" in capsys.readouterr().err

