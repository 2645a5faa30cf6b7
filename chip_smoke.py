#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives the main path (embed -> retrieve -> generate behind ``/embeddings`` and
``/dialog``) once, through the entry points a user runs, on ONE TPU chip, at
the published widths of Mistral-7B-v0.1 (int8 weight-only, seeded random
weights) and the default ruBert-base-geometry encoder:

- *weights*: a child draws the seeded checkpoints on the device and saves them
  under ``.cache/chip_smoke/`` (skipped when they are already there);
- *serve*: ``python -m django_assistant_bot_tpu.cli serve --config <toml>
  --warmup`` as a child; this process waits on ``/healthz`` and sends real HTTP
  requests (embeddings, short/long/streamed/JSON dialogs, two concurrent
  requests sharing a prefix), checks them, then SIGTERMs the child, which must
  drain and exit 0;
- *rag*: a child with the in-process ``tpu:`` provider builds an exact
  100,000 x 768 ``VectorIndex`` and answers three RAG turns through
  ``rag/services/search_service.py``, checking the device's top-k against a
  NumPy float32 top-k.

A chip belongs to one process at a time, so this process never initialises a
JAX backend and each child has exited before the next starts.  Every child
names its device; anything but ``tpu`` is refused, nothing falls back, and
``"ok": true`` is printed for no other platform.  The last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--multichip`` (four chips, run by hand) runs instead, in one process: the
same decoder on device 0 alone and tensor-parallel over a 4-device mesh
(prefill logits compared, per-device resident bytes checked), then two
replicas x TP-2 on disjoint slices behind the router.

Times printed are set-up and per-request wall times from one run — facts about
whether the system starts, not rates (PERF.md).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EMB_MODEL = "smoke-emb"
CHAT_MODEL = "smoke-chat"

# mistralai/Mistral-7B-v0.1 config.json, as DecoderConfig fields.  Widths are
# never cut; a depth cut (num_layers) would be printed and recorded.
MISTRAL_7B_V01 = dict(
    vocab_size=32_000,
    hidden_size=4096,
    intermediate_size=14_336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=32_768,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    sliding_window=4096,
)
PUBLISHED_LAYERS = 32


class SmokeFailure(Exception):
    """A phase could not run, or a check on what it produced failed."""


@dataclasses.dataclass
class SmokeConfig:
    """Everything a phase needs.  ``full_config()`` is the only configuration
    ``__main__`` ever runs; tests hand the phase functions a tiny one with
    ``require_tpu=False`` — the script itself has no such option."""

    work_dir: str
    decoder: Dict[str, Any]
    encoder: Dict[str, Any]  # {} = EncoderConfig() — the ruBert-base geometry
    int8: bool = True
    dtype: str = "bfloat16"
    max_seq_len: int = 2048
    max_slots: int = 8
    chunk_size: int = 1024
    max_batch: int = 32
    corpus_rows: int = 100_000
    short_tokens: int = 16
    long_prompt_range: tuple = (512, 1024)  # tokens; >= 256 takes the flash path
    logits_rel_rms_tol: float = 0.05
    seed: int = 0
    require_tpu: bool = True

    @property
    def toml_path(self) -> str:
        return os.path.join(self.work_dir, "serving.toml")

    @property
    def encoder_dir(self) -> str:
        return os.path.join(self.work_dir, "encoder")

    @property
    def decoder_dir(self) -> str:
        return os.path.join(self.work_dir, "decoder")

    @property
    def log_dir(self) -> str:
        return os.path.join(ROOT, "chiprun_out", "chip_smoke")


def full_config() -> SmokeConfig:
    return SmokeConfig(
        work_dir=os.path.join(ROOT, ".cache", "chip_smoke"),
        decoder=dict(MISTRAL_7B_V01),
        encoder={},
    )


# --------------------------------------------------------------- small helpers
class Checks:
    """Named pass/fail facts about one phase; the phase fails if any did."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: List[str] = []
        self.passed = 0

    def check(self, name: str, ok: bool, detail: Any = "") -> bool:
        if ok:
            self.passed += 1
        else:
            self.failed.append(f"{name}: {detail}" if detail != "" else name)
        return bool(ok)

    def finish(self) -> None:
        if self.failed:
            raise SmokeFailure(f"{self.phase}: " + "; ".join(self.failed))


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def _tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _require_device(cfg: SmokeConfig, need: int = 1) -> Dict[str, Any]:
    """Name this process's device; refuse anything but a TPU (JAX children only)."""
    from django_assistant_bot_tpu.utils.device import device_info

    info = device_info()
    _check_device(cfg, info, need)
    return info


def _check_device(cfg: SmokeConfig, info: Dict[str, Any], need: int = 1) -> None:
    if cfg.require_tpu and info.get("platform") != "tpu":
        raise SmokeFailure(
            f"device is {info.get('platform')!r} ({info.get('kind')}), not a TPU: "
            "chip_smoke.py runs on the chip only and never falls back"
        )
    if int(info.get("count", 0)) < need:
        raise SmokeFailure(f"need {need} device(s), JAX reports {info.get('count')}")


def _model_configs(cfg: SmokeConfig):
    import jax.numpy as jnp

    from django_assistant_bot_tpu.models.config import DecoderConfig, EncoderConfig

    dtype = getattr(jnp, cfg.dtype)
    return (
        EncoderConfig(**{"dtype": dtype, **cfg.encoder}),
        DecoderConfig(**{"dtype": dtype, **cfg.decoder}),
    )


def write_serving_config(cfg: SmokeConfig) -> str:
    """The TOML both ``serve --config`` and ``DABT_TPU_SERVING_CONFIG`` read."""
    os.makedirs(cfg.work_dir, exist_ok=True)
    lines = [
        f"[models.{EMB_MODEL}]",
        'kind = "encoder"',
        f"checkpoint = {json.dumps(cfg.encoder_dir)}",
        f'dtype = "{cfg.dtype}"',
        f"max_batch = {cfg.max_batch}",
        "",
        f"[models.{CHAT_MODEL}]",
        'kind = "decoder"',
        f"checkpoint = {json.dumps(cfg.decoder_dir)}",
        f'dtype = "{cfg.dtype}"',
        f"max_seq_len = {cfg.max_seq_len}",
        f"max_slots = {cfg.max_slots}",
        f"chunk_size = {cfg.chunk_size}",
    ]
    if cfg.int8:
        lines.append('quantize = "int8"')
    with open(cfg.toml_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return cfg.toml_path


def _filler(n_chars: int, tag: str) -> str:
    """Deterministic ASCII text of exactly ``n_chars`` (one byte-token each)."""
    text, i = "", 0
    while len(text) < n_chars:
        text += f"{tag}{i} "
        i += 1
    return text[:n_chars]


# --------------------------------------------------------------- phase: weights
def phase_weights(cfg: SmokeConfig) -> Dict[str, Any]:
    """Draw the seeded checkpoints on the device and save them where the
    server's own loader (``ModelSpec.checkpoint``) will read them."""
    t0 = time.monotonic()
    from django_assistant_bot_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    device = _require_device(cfg)
    from django_assistant_bot_tpu.models.synth import synth_native

    ecfg, dcfg = _model_configs(cfg)
    t1 = time.monotonic()
    made_enc = synth_native(cfg.encoder_dir, "encoder", ecfg, seed=cfg.seed + 1)
    t2 = time.monotonic()
    made_dec = synth_native(
        cfg.decoder_dir, "decoder", dcfg, seed=cfg.seed, int8=cfg.int8
    )
    t3 = time.monotonic()

    def size(d: str) -> int:
        return sum(e.stat().st_size for e in os.scandir(d) if e.is_file())

    return {
        "phase": "weights",
        "device": device,
        "decoder_layers": dcfg.num_layers,
        "generated": {"encoder": made_enc, "decoder": made_dec},
        "bytes": {"encoder": size(cfg.encoder_dir), "decoder": size(cfg.decoder_dir)},
        "backend_s": round(t1 - t0, 2),
        "encoder_s": round(t2 - t1, 2),
        "decoder_s": round(t3 - t2, 2),
    }


# ----------------------------------------------------------------- phase: serve
def _http_raw(port: int, path: str, body: Optional[dict] = None, timeout: float = 600.0):
    """One HTTP exchange -> (status, content type, body text)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, ctype, raw = r.status, r.headers.get("Content-Type", ""), r.read()
    except urllib.error.HTTPError as e:
        status, ctype, raw = e.code, e.headers.get("Content-Type", ""), e.read()
    return status, ctype, raw.decode("utf-8", errors="replace")


def _http(port: int, path: str, body: Optional[dict] = None, timeout: float = 600.0):
    """One HTTP exchange -> (status, parsed JSON, or the text when it is not JSON)."""
    status, _, raw = _http_raw(port, path, body, timeout)
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def _sse_events(raw: str) -> List[Any]:
    out = []
    for block in raw.split("\n\n"):
        block = block.strip()
        if block.startswith("data: "):
            data = block[len("data: "):]
            out.append(data if data == "[DONE]" else json.loads(data))
    return out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _finite_matrix(rows: Any, n: int, dim: int) -> bool:
    import math

    return (
        isinstance(rows, list)
        and len(rows) == n
        and all(
            isinstance(r, list)
            and len(r) == dim
            and all(isinstance(x, (int, float)) and math.isfinite(x) for x in r)
            for r in rows
        )
    )


def phase_serve(cfg: SmokeConfig, boot_timeout_s: float = 1000.0) -> Dict[str, Any]:
    """Boot ``cli serve --warmup`` as a child and drive it over real HTTP.
    Stdlib only: this runs in the parent, which must stay off JAX."""
    checks = Checks("serve")
    os.makedirs(cfg.log_dir, exist_ok=True)
    log_path = os.path.join(cfg.log_dir, "serve.log")
    port = _free_port()
    cmd = [
        sys.executable, "-m", "django_assistant_bot_tpu.cli", "serve",
        "--config", cfg.toml_path, "--warmup",
        "--host", "127.0.0.1", "--port", str(port),
    ]
    t_spawn = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT)
    try:
        health: Any = None
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"serve exited rc={proc.returncode} before /healthz answered\n"
                    + _tail(log_path)
                )
            try:
                status, health = _http(port, "/healthz", timeout=5.0)
                if status == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() - t_spawn > boot_timeout_s:
                raise SmokeFailure(
                    f"serve did not answer /healthz in {boot_timeout_s:.0f}s\n"
                    + _tail(log_path)
                )
            time.sleep(1.0)
        boot_s = time.monotonic() - t_spawn
        device = health.get("device") or {}
        _check_device(cfg, device)
        boot = health.get("boot_s", {})

        walls: Dict[str, float] = {}

        def timed(name: str, fn: Callable[[], Any]) -> Any:
            t0 = time.monotonic()
            out = fn()
            walls[name] = round(time.monotonic() - t0, 3)
            return out

        def dialog(messages, **kw):
            body = {"model": CHAT_MODEL, "messages": messages, **kw}
            return _http(port, "/dialog/", body)

        def usage_ok(name: str, status: int, data: Any, max_tokens: int, lo: int, hi: int):
            if not checks.check(f"{name} status", status == 200, f"{status} {data}"):
                return None
            u = data["response"]["usage"]
            checks.check(
                f"{name} prompt_tokens", lo <= u["prompt_tokens"] <= hi, u
            )
            checks.check(
                f"{name} completion_tokens",
                1 <= u["completion_tokens"] <= max_tokens
                and u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"],
                u,
            )
            return u

        # --- /embeddings/: one text, then a full batch -----------------------
        status, data = timed(
            "embed_1",
            lambda: _http(port, "/embeddings/", {"model": EMB_MODEL, "texts": ["what is paged KV?"]}),
        )
        dim = 0
        if checks.check("embed_1 status", status == 200, f"{status} {data}"):
            dim = len(data["embeddings"][0])
            checks.check("embed_1 shape+finite", _finite_matrix(data["embeddings"], 1, dim))
        texts = [f"document {i}: " + _filler(40 + i, f"w{i}_") for i in range(cfg.max_batch)]
        status, data = timed(
            f"embed_{cfg.max_batch}",
            lambda: _http(port, "/embeddings/", {"model": EMB_MODEL, "texts": texts}),
        )
        if checks.check("embed_batch status", status == 200, f"{status} {str(data)[:200]}"):
            checks.check(
                "embed_batch shape+finite",
                _finite_matrix(data["embeddings"], cfg.max_batch, dim),
            )

        # --- /dialog/: short prompt (jnp prefill path), greedy ---------------
        n_new = cfg.short_tokens
        short = [{"role": "user", "content": "Say hello to the chip."}]
        greedy = dict(max_tokens=n_new, temperature=0.0)
        status, data = timed("dialog_short", lambda: dialog(short, **greedy))
        buffered = None
        if usage_ok("dialog_short", status, data, n_new, 1, 255) is not None:
            buffered = data["response"]

        # --- /dialog/: 512-1024-token prompt (whole-bucket prefill >= 256 ->
        # the flash kernel), whose system block later requests share ----------
        lo, hi = cfg.long_prompt_range
        system = "Answer from context:\n" + _filler((lo + hi) // 2 - 64, "fact")
        long_msgs = [
            {"role": "system", "content": system},
            {"role": "user", "content": "first question about the context?"},
        ]
        status, data = timed("dialog_long", lambda: dialog(long_msgs, **greedy))
        usage_ok("dialog_long", status, data, n_new, lo, hi)

        # --- "stream": true — same greedy request as dialog_short ------------
        status, ctype, raw = timed(
            "dialog_stream",
            lambda: _http_raw(
                port, "/dialog/",
                {"model": CHAT_MODEL, "messages": short, "stream": True, **greedy},
            ),
        )
        if checks.check("dialog_stream status", status == 200, f"{status} {raw[:200]}"):
            events = _sse_events(raw)
            done = [e for e in events if isinstance(e, dict) and e.get("done")]
            deltas = "".join(
                e["delta"] for e in events if isinstance(e, dict) and "delta" in e
            )
            checks.check("dialog_stream event-stream", "text/event-stream" in ctype, ctype)
            checks.check(
                "dialog_stream terminal",
                len(done) == 1 and events[-1] == "[DONE]" and "error" not in done[0],
                events[-3:],
            )
            if done and buffered is not None:
                checks.check(
                    "streamed text == buffered text",
                    deltas == done[0].get("result") == buffered["result"],
                    (deltas, done[0].get("result"), buffered["result"]),
                )
                checks.check(
                    "streamed tokens == buffered tokens",
                    done[0]["usage"]["completion_tokens"]
                    == buffered["usage"]["completion_tokens"],
                    (done[0]["usage"], buffered["usage"]),
                )

        # --- "json_format": true — grammar-constrained; sampled, so a run may
        # spend its whole budget inside a value: retry, but a finished answer
        # must parse -------------------------------------------------------
        json_msgs = [{"role": "user", "content": "Reply with a JSON object."}]
        attempts: List[str] = []
        parsed_ok = False
        t0 = time.monotonic()
        for _ in range(8):
            status, data = dialog(json_msgs, max_tokens=256, temperature=0.8, json_format=True)
            if not checks.check("dialog_json status", status == 200, f"{status} {data}"):
                break
            resp = data["response"]
            if resp["length_limited"]:
                attempts.append("length_limited")
                continue
            try:
                obj = json.loads(resp["result"])
            except ValueError:
                attempts.append("unparseable")
                checks.check("dialog_json parses", False, resp["result"][:200])
                break
            attempts.append("parsed")
            parsed_ok = isinstance(obj, (dict, list))
            break
        walls["dialog_json"] = round(time.monotonic() - t0, 3)
        checks.check("dialog_json parsed within 8 attempts", parsed_ok, attempts)

        # --- two concurrent requests sharing the long system prefix ---------
        results: Dict[int, Any] = {}

        def shared(i: int) -> None:
            msgs = [long_msgs[0], {"role": "user", "content": f"follow-up question {i}?"}]
            results[i] = dialog(msgs, **greedy)

        t0 = time.monotonic()
        threads = [threading.Thread(target=shared, args=(i,)) for i in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900.0)
        walls["dialog_shared_x2"] = round(time.monotonic() - t0, 3)
        for i in (1, 2):
            status, data = results.get(i, (0, "no answer"))
            usage_ok(f"dialog_shared_{i}", status, data, n_new, lo, hi)

        # --- /healthz after traffic -----------------------------------------
        status, health = _http(port, "/healthz")
        checks.check(
            "healthz status", status == 200 and health.get("status") == "ok", str(health)[:200]
        )
        gen = health["generators"][CHAT_MODEL]
        sup = gen.get("supervision", {})
        checks.check(
            "zero restarts, zero quarantines",
            sup.get("engine_restarts") == 0 and sup.get("poisoned_requests") == 0,
            sup,
        )
        kv = gen.get("kv", {})
        checks.check(
            "prefix pages shared",
            kv.get("prefix_hits", 0) >= 2 and kv.get("kv_shared_pages", 0) >= 1,
            {k: v for k, v in kv.items() if k.startswith(("prefix_", "kv_shared"))},
        )
        checks.check(
            "decoder is the configured one",
            gen.get("decode", {}).get("weight_bits") == (8 if cfg.int8 else 16),
            gen.get("decode"),
        )
        # on a TPU the decode step's K/V write and read are the Pallas kernel
        # (no fallback there); the tests' tiny CPU run takes the plain path
        kv_path = gen.get("decode", {}).get("decode_kv_path")
        checks.check(
            "decode K/V path",
            kv_path == ("kernel" if device.get("platform") == "tpu" else "xla"),
            gen.get("decode"),
        )

        # --- SIGTERM: drain and exit 0 ---------------------------------------
        t0 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120.0)
        except subprocess.TimeoutExpired:
            rc = None
        walls["sigterm_drain"] = round(time.monotonic() - t0, 3)
        checks.check("SIGTERM -> exit 0", rc == 0, f"rc={rc}\n{_tail(log_path, 30)}")
        if checks.failed:
            _say("serve log tail:\n" + _tail(log_path, 40))
        checks.finish()
        return {
            "phase": "serve",
            "device": device,
            "boot_s": round(boot_s, 2),
            "model_boot_s": boot,
            "request_wall_s": walls,
            "json_attempts": attempts,
            "prefix": {k: kv.get(k) for k in ("prefix_hits", "prefix_misses", "kv_shared_pages")},
            "decode_kv_path": kv_path,
            "checks_passed": checks.passed,
        }
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30.0)


# ------------------------------------------------------------------- phase: rag
@contextlib.contextmanager
def _rag_environment(cfg: SmokeConfig, dim: int):
    """Point the framework at the smoke's TOML, models and a fresh sqlite file
    the way a deployment does — through ``DABT_*`` variables — and put
    everything back afterwards."""
    from django_assistant_bot_tpu.ai.providers.tpu import reset_shared_registry
    from django_assistant_bot_tpu.conf import settings
    from django_assistant_bot_tpu.rag.index_registry import reset_indexes
    from django_assistant_bot_tpu.storage import db

    db_path = os.path.join(cfg.work_dir, "rag.sqlite3")
    env = {
        "DABT_TPU_SERVING_CONFIG": cfg.toml_path,
        "DABT_EMBEDDING_AI_MODEL": f"tpu:{EMB_MODEL}",
        "DABT_DEFAULT_AI_MODEL": f"tpu:{CHAT_MODEL}",
        "DABT_EMBEDDING_DIM": str(dim),
        "DABT_DB_PATH": db_path,
    }
    saved = {k: os.environ.get(k) for k in env}

    def clean_db():
        for suffix in ("", "-wal", "-shm"):
            with contextlib.suppress(FileNotFoundError):
                os.remove(db_path + suffix)

    os.environ.update(env)
    settings.reload()
    db.reset_default_database()
    reset_shared_registry()
    reset_indexes()
    clean_db()
    try:
        yield
    finally:
        reset_shared_registry()
        reset_indexes()
        db.reset_default_database()
        clean_db()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        settings.reload()


def _load_corpus(cfg: SmokeConfig, dim: int):
    """Seeded vectors into sqlite in bulk (set-up, not the path under test:
    the index is then built from these rows through the ORM)."""
    import numpy as np

    from django_assistant_bot_tpu.storage.db import get_database
    from django_assistant_bot_tpu.storage.models import Document, Question

    n = cfg.corpus_rows
    n_docs = max(1, n // 100)
    vecs = np.random.default_rng(cfg.seed + 7).standard_normal((n, dim), dtype=np.float32)
    database = get_database()
    database.ensure_table(Question)
    conn = database.connection()
    conn.executemany(
        'INSERT INTO document (id, name, content) VALUES (?, ?, ?)',
        (
            (d + 1, f"doc-{d}", f"Document {d}: " + _filler(160, f"d{d}f"))
            for d in range(n_docs)
        ),
    )
    conn.executemany(
        'INSERT INTO question (id, document_id, text, "order", embedding) '
        "VALUES (?, ?, ?, 0, ?)",
        (
            (i + 1, i % n_docs + 1, f"question {i}?", vecs[i].tobytes())
            for i in range(n)
        ),
    )
    conn.commit()
    assert Document.objects.count() == n_docs
    return vecs  # row i is Question id i + 1


def _indexed_rows(vecs):
    """Float32 values of the rows as ``storage/knn.py`` holds them on the
    device: rounded to bf16, normalised, rounded again."""
    import ml_dtypes
    import numpy as np

    bf16 = ml_dtypes.bfloat16
    rows = vecs.astype(bf16).astype(np.float32)
    rows /= np.maximum(np.linalg.norm(rows, axis=1, keepdims=True), 1e-12)
    return rows.astype(bf16).astype(np.float32)


def _reference_topk(rows, query, k: int):
    """NumPy float32 top-k over :func:`_indexed_rows`; the query as the index
    treats it: normalised in f32 on the host, rounded to bf16 in the kernel."""
    import ml_dtypes
    import numpy as np

    q = np.asarray(query, np.float32)
    q = (q / max(float(np.linalg.norm(q)), 1e-12)).astype(ml_dtypes.bfloat16)
    scores = rows @ q.astype(np.float32)
    return np.argsort(-scores, kind="stable")[:k], scores


def phase_rag(cfg: SmokeConfig, k: int = 10) -> Dict[str, Any]:
    """Engine and index in ONE process through the ``tpu:`` provider: embed the
    query, top-k on the device, generate from the packed context."""
    import asyncio

    t0 = time.monotonic()
    from django_assistant_bot_tpu.utils.compile_cache import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()
    device = _require_device(cfg)
    checks = Checks("rag")
    ecfg, _ = _model_configs(cfg)
    dim = ecfg.hidden_size
    with _rag_environment(cfg, dim):
        from django_assistant_bot_tpu.ai.providers.tpu import get_shared_registry
        from django_assistant_bot_tpu.ai.services.ai_service import get_ai_provider
        from django_assistant_bot_tpu.conf import settings
        from django_assistant_bot_tpu.native import native_available
        from django_assistant_bot_tpu.rag.index_registry import get_index
        from django_assistant_bot_tpu.rag.services import search_service
        from django_assistant_bot_tpu.storage.models import Question

        t1 = time.monotonic()
        registry = get_shared_registry()  # loads both models from the TOML
        specs = registry.specs
        checks.check(
            "tpu: provider serves the TOML's checkpoints",
            specs.get(EMB_MODEL) is not None
            and specs[EMB_MODEL].checkpoint == cfg.encoder_dir
            and specs.get(CHAT_MODEL) is not None
            and specs[CHAT_MODEL].checkpoint == cfg.decoder_dir,
            sorted(specs),
        )
        checks.finish()
        t2 = time.monotonic()
        rows = _indexed_rows(_load_corpus(cfg, dim))
        t3 = time.monotonic()
        index = get_index(Question, "embedding")  # build + stage + warm
        t4 = time.monotonic()
        checks.check(
            "exact VectorIndex serves the corpus",
            type(index).__name__ == "VectorIndex" and len(index) == cfg.corpus_rows,
            (type(index).__name__, len(index)),
        )
        provider = get_ai_provider(settings.DEFAULT_AI_MODEL)

        async def turn(i: int) -> Dict[str, Any]:
            query = f"benchmark question number {i} about topic {i % 7}?"
            ta = time.monotonic()
            emb = await search_service.get_embedding(query)
            tb = time.monotonic()
            hits = await search_service.embedding_search_questions(emb, n=k)
            tc = time.monotonic()
            got = [h.id - 1 for h in hits]
            want, scores = _reference_topk(rows, emb, k)
            exact = got == want.tolist()
            # rows whose f32 normalisation lands within one bf16 rounding of a
            # boundary may round differently on the device; that moves a score
            # by < 2e-5, so positions may swap only between such near-ties
            near = len(got) == k and all(
                abs(float(scores[g]) - float(scores[w])) <= 2e-5
                for g, w in zip(got, want)
            )
            checks.check(
                f"turn {i} device top-{k} == NumPy float32 top-{k}",
                exact or near,
                (got, want.tolist()),
            )
            docs = await search_service.embedding_search(
                query, Question, max_scores_n=1, top_n=3
            )
            checks.check(f"turn {i} documents found", len(docs) == 3, len(docs))
            context = "\n".join(d.content for d, _ in docs)
            td = time.monotonic()
            resp = await provider.get_response(
                [
                    {"role": "system", "content": "Answer from context:\n" + context},
                    {"role": "user", "content": query},
                ],
                max_tokens=cfg.short_tokens,
            )
            te = time.monotonic()
            u = resp.usage
            checks.check(
                f"turn {i} generated",
                1 <= u["completion_tokens"] <= cfg.short_tokens
                and u["prompt_tokens"] > len(context),
                u,
            )
            return {
                "embed_s": round(tb - ta, 3),
                "search_s": round(tc - tb, 3),
                "generate_s": round(te - td, 3),
                "topk_exact": exact,
                "prompt_tokens": u["prompt_tokens"],
                "completion_tokens": u["completion_tokens"],
            }

        async def turns():
            return [await turn(i) for i in range(3)]

        turn_stats = asyncio.run(turns())
        embedder = registry.get_embedder(EMB_MODEL)
        tokenizer = type(embedder.tokenizer).__name__
        native = native_available()
        gen = registry.get_generator(CHAT_MODEL)
        sup = gen.supervision_stats()
        checks.check(
            "zero restarts, zero quarantines",
            sup.get("engine_restarts") == 0 and sup.get("poisoned_requests") == 0,
            sup,
        )
    checks.finish()
    return {
        "phase": "rag",
        "device": device,
        "compile_cache": cache_dir,
        "backend_s": round(t1 - t0, 2),
        "load_s": round(t2 - t1, 2),
        "corpus_rows": cfg.corpus_rows,
        "corpus_insert_s": round(t3 - t2, 2),
        "index_build_s": round(t4 - t3, 2),
        "turns": turn_stats,
        # which tokenizer served the embedding requests: the seeded checkpoint
        # ships no vocab, so the serving plane's Python byte tokenizer does;
        # the C++ WordPiece library builds here but nothing in serving loads it
        "embed_tokenizer": f"python:{tokenizer}",
        "native_wordpiece_built": bool(native),
        "checks_passed": checks.passed,
    }


# ------------------------------------------------------------- phase: multichip
def phase_multichip(cfg: SmokeConfig, new_tokens: int = 8) -> Dict[str, Any]:
    """One process, four devices: device 0 alone vs a ``model=4`` mesh through
    ``GenerationEngine``, then two replicas x TP-2 behind ``EngineRouter``."""
    t0 = time.monotonic()
    from django_assistant_bot_tpu.utils.compile_cache import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()
    device = _require_device(cfg, need=4)
    import jax
    import numpy as np

    from django_assistant_bot_tpu.models import llama
    from django_assistant_bot_tpu.ops.quant import QUANTIZABLE
    from django_assistant_bot_tpu.parallel import MeshAxes, MeshPlanner, make_mesh, shard_pytree
    from django_assistant_bot_tpu.serving import ByteTokenizer, EngineRouter, GenerationEngine
    from django_assistant_bot_tpu.serving.engine import pick_bucket

    checks = Checks("multichip")
    devices = jax.devices()[:4]
    _, dcfg = _model_configs(cfg)
    tok = ByteTokenizer()
    lo, hi = cfg.long_prompt_range
    prompts = {
        "short": tok.encode("user: Say hello to the chips.\nassistant:"),
        "long": tok.encode("user: " + _filler((lo + hi) // 2, "fact") + "\nassistant:"),
    }
    checks.check(
        f"long prompt of {lo}..chunk_size tokens (one whole-bucket prefill)",
        lo <= len(prompts["long"]) <= cfg.chunk_size,
        len(prompts["long"]),
    )
    checks.finish()
    engine_kw = dict(
        max_slots=2,
        max_seq_len=cfg.max_seq_len,
        chunk_size=cfg.chunk_size,
        prefix_cache_size=0,
    )
    with jax.default_device(devices[0]):
        make = llama.init_int8 if cfg.int8 else llama.init
        source = make(dcfg, jax.random.key(cfg.seed))
    jax.block_until_ready(source)
    t_init = time.monotonic()

    def no_restarts(eng, who: str) -> None:
        # supervision restarts a crashed engine and the router re-routes its
        # requests, so answers alone do not show that a replica halted
        sup = eng.supervision_stats()
        checks.check(
            f"{who}: zero restarts, zero quarantines",
            sup.get("engine_restarts") == 0 and sup.get("poisoned_requests") == 0,
            {k: sup.get(k) for k in ("engine_restarts", "poisoned_requests")},
        )

    def run(params, mesh) -> Dict[str, Any]:
        """Prefill logits (the engine's own jitted prefill) and greedy tokens
        through the engine, for every prompt."""
        eng = GenerationEngine(dcfg, params, tok, mesh=mesh, **engine_kw)
        out: Dict[str, Any] = {"logits": {}, "tokens": {}, "kernel": {}}
        try:
            for name, ids in prompts.items():
                bucket = pick_bucket(len(ids), eng.prefill_shapes, eng.chunk_size)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, : len(ids)] = ids
                lengths = np.asarray([len(ids)], np.int32)
                with eng._mesh_scope():
                    out["kernel"][name] = "tpu_custom_call" in eng._prefill.lower(
                        eng.params, padded, lengths
                    ).as_text()
                    logits, _, _ = eng._prefill(eng.params, padded, lengths)
                out["logits"][name] = np.asarray(logits[0], np.float32)
            eng.start()
            futs = {
                name: eng.submit(ids, max_tokens=new_tokens, temperature=0.0)
                for name, ids in prompts.items()
            }
            for name, f in futs.items():
                out["tokens"][name] = list(f.result(timeout=900.0).token_ids)
            no_restarts(eng, "one device" if mesh is None else "TP-4")
        finally:
            eng.stop(drain_timeout_s=60.0)
        return out

    mesh4 = make_mesh(MeshAxes(model=4), devices=devices)
    with mesh4:
        params4 = shard_pytree(source, llama.logical_axes(dcfg), mesh4)
    jax.block_until_ready(params4)

    # bytes resident per device, from the arrays' own shards — checked before
    # any engine runs: everything on the first device, or everything
    # replicated, is the expected fault of code that never saw four chips
    def bytes_by_device(tree) -> Dict[int, int]:
        out = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                out[sh.device.id] += int(sh.data.nbytes)
        return out

    proj = [params4["layers"][k] for k in QUANTIZABLE if k in params4["layers"]]
    logical = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(proj))
    per_dev, total_dev = bytes_by_device(proj), bytes_by_device(params4)
    shares = {d: round(b / logical, 4) for d, b in per_dev.items()}
    checks.check(
        "each device holds 1/5..2/5 of the layer-projection bytes",
        all(0.2 <= s <= 0.4 for s in shares.values()),
        shares,
    )
    checks.finish()
    t_shard = time.monotonic()

    one = run(source, None)
    t_one = time.monotonic()
    tp4 = run(params4, mesh4)
    t_tp4 = time.monotonic()
    compare = {}
    for name in prompts:
        a, b = one["logits"][name], tp4["logits"][name]
        finite = bool(np.isfinite(a).all() and np.isfinite(b).all())
        rel = float(np.sqrt(np.mean((a - b) ** 2)) / max(float(np.sqrt(np.mean(a**2))), 1e-12))
        ta, tb = one["tokens"][name], tp4["tokens"][name]
        first_diff = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y), None)
        compare[name] = {
            "prompt_tokens": len(prompts[name]),
            "logits_rel_rms": round(rel, 5),
            "logits_max_abs": round(float(np.max(np.abs(a - b))), 4),
            "first_greedy_step_differing": first_diff,
            "kernel_in_prefill": {"one": one["kernel"][name], "tp4": tp4["kernel"][name]},
        }
        checks.check(f"{name}: logits finite", finite)
        # bf16 unit roundoff 2^-9, ~6 roundings a layer, random-walk over L
        # layers: 2^-9 * sqrt(6L) ~ 2.7% at L=32 — bounded at about twice
        # that.  A wrong shard or a missing reduction is ~100%.
        checks.check(
            f"{name}: TP-4 logits within {cfg.logits_rel_rms_tol:.0%} rel. RMS of one device",
            finite and rel <= cfg.logits_rel_rms_tol,
            rel,
        )
        checks.check(
            f"{name}: {new_tokens} tokens from both",
            len(ta) == new_tokens and len(tb) == new_tokens,
            (ta, tb),
        )
    if device["platform"] == "tpu":
        checks.check(
            "long prompt went through the Pallas kernel under the mesh",
            tp4["kernel"]["long"] and one["kernel"]["long"] and not tp4["kernel"]["short"],
            {n: c["kernel_in_prefill"] for n, c in compare.items()},
        )
    del params4, tp4
    gc.collect()

    # --- two replicas x TP-2 on disjoint slices behind the router ------------
    planner = MeshPlanner(2, devices=devices)
    engines = []
    try:
        for _ in range(planner.n_slices):
            sl = planner.acquire()
            with sl.mesh:
                rp = shard_pytree(source, llama.logical_axes(dcfg), sl.mesh)
            eng = GenerationEngine(dcfg, rp, tok, mesh=sl.mesh, **engine_kw)
            eng.slice_id = sl.slice_id
            engines.append(eng.start())
        placements = [
            sorted({d.id for leaf in jax.tree.leaves(e.params) for d in leaf.sharding.device_set})
            for e in engines
        ]
        checks.check(
            "replica slices disjoint, two devices each",
            len(engines) == 2
            and all(len(p) == 2 for p in placements)
            and not set(placements[0]) & set(placements[1]),
            placements,
        )
        router = EngineRouter(engines, names=["slice0", "slice1"])
        futs = [
            router.submit(tok.encode(f"user: request {i}\nassistant:"), max_tokens=4, temperature=0.0)
            for i in range(4)
        ]
        done = [f.result(timeout=900.0) for f in futs]
        checks.check(
            "four routed requests, four tokens each",
            all(len(r.token_ids) == 4 for r in done),
            [r.token_ids for r in done],
        )
        for name, e in zip(("slice0", "slice1"), engines):
            no_restarts(e, f"replica {name}")
        replica_steps = [e.steps for e in engines]
    finally:
        for e in engines:
            e.stop(drain_timeout_s=60.0)
    t_end = time.monotonic()
    checks.finish()
    return {
        "phase": "multichip",
        "device": device,
        "compile_cache": cache_dir,
        "decoder_layers": dcfg.num_layers,
        "compare": compare,
        "resident_bytes_per_device": total_dev,
        "projection_bytes_per_device": per_dev,
        "projection_share_per_device": shares,
        "replica_devices": placements,
        "replica_steps": replica_steps,
        "init_s": round(t_init - t0, 2),
        "shard_s": round(t_shard - t_init, 2),
        "one_device_s": round(t_one - t_shard, 2),
        "tp4_s": round(t_tp4 - t_one, 2),
        "replicas_s": round(t_end - t_tp4, 2),
        "checks_passed": checks.passed,
    }


# ------------------------------------------------------------------ entry point
PHASES = {"weights": phase_weights, "rag": phase_rag, "multichip": phase_multichip}


def _run_child(cfg: SmokeConfig, phase: str, timeout_s: float) -> Dict[str, Any]:
    """Run one JAX phase as a child that has exited before this returns; its
    last stdout line is its JSON summary."""
    os.makedirs(cfg.log_dir, exist_ok=True)
    log_path = os.path.join(cfg.log_dir, f"{phase}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", phase],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=log, text=True,
        )
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            raise SmokeFailure(f"{phase} child exceeded {timeout_s:.0f}s\n" + _tail(log_path)) from None
    if proc.returncode != 0:
        raise SmokeFailure(f"{phase} child exited rc={proc.returncode}\n" + _tail(log_path))
    lines = [line for line in out.strip().splitlines() if line.startswith("{")]
    if not lines:
        raise SmokeFailure(f"{phase} child printed no summary\n" + _tail(log_path))
    summary = json.loads(lines[-1])
    _check_device(cfg, summary.get("device") or {})
    return summary


def _same_device(summaries: List[Dict[str, Any]]) -> Dict[str, Any]:
    devices = [s["device"] for s in summaries]
    if any(d != devices[0] for d in devices):
        raise SmokeFailure(f"phases disagree on the device: {devices}")
    return devices[0]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--multichip", action="store_true",
        help="four chips: run ONLY the one-device vs TP-4 comparison and the "
        "2 x TP-2 replica fleet",
    )
    parser.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    cfg = full_config()
    try:
        if args.phase:  # a child: one JAX phase, one JSON line
            print(json.dumps(PHASES[args.phase](cfg)), flush=True)
            return 0
        if cfg.decoder["num_layers"] != PUBLISHED_LAYERS:
            print(
                f"depth cut: {cfg.decoder['num_layers']} of {PUBLISHED_LAYERS} "
                "published layers (widths unchanged)",
                flush=True,
            )
        summaries: List[Dict[str, Any]] = []

        def done(summary: Dict[str, Any]) -> None:
            # a phase's line goes out as soon as its device has been accepted
            # and its checks have passed, so a later failure keeps the facts
            summaries.append(summary)
            print(json.dumps(summary), flush=True)

        if args.multichip:
            done(phase_multichip(cfg))
        else:
            done(_run_child(cfg, "weights", 600.0))
            write_serving_config(cfg)
            done(phase_serve(cfg))
            done(_run_child(cfg, "rag", 600.0))
        device = _same_device(summaries)
    except SmokeFailure as e:
        _say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
