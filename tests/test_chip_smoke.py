"""CPU rehearsals of ``chip_smoke.py`` (on-chip-measurement §2.1/§2.2): the
phase functions at a tiny size, with the permission to run off the chip that
the script's own ``__main__`` never grants — and the proof that it doesn't."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _tiny(tmp_path, **kw):
    cfg = chip_smoke.SmokeConfig(
        work_dir=str(tmp_path / "work"),
        decoder=dict(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=2, max_seq_len=1024, rope_theta=10_000.0,
            sliding_window=512,
        ),
        encoder=dict(
            vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, max_position_embeddings=128,
        ),
        dtype="float32",
        max_seq_len=1024,
        max_slots=2,
        chunk_size=128,
        max_batch=4,
        corpus_rows=2000,
        short_tokens=6,
        long_prompt_range=(260, 420),
        require_tpu=False,
    )
    return chip_smoke.dataclasses.replace(cfg, **kw)


# heads and KV heads that divide a model=4 mesh
_TP4_DECODER = dict(
    vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
    num_heads=8, num_kv_heads=4, head_dim=16, max_seq_len=1024,
    rope_theta=10_000.0, sliding_window=512,
)


def test_smoke_phases_rehearse_on_cpu(tmp_path, monkeypatch):
    """weights -> serve (a real ``cli serve --warmup`` child over HTTP) -> rag,
    exactly the calls ``main()`` makes, at tiny size on the CPU."""
    cfg = _tiny(tmp_path)
    monkeypatch.setattr(
        chip_smoke.SmokeConfig, "log_dir", property(lambda self: str(tmp_path / "logs"))
    )
    w = chip_smoke.phase_weights(cfg)
    assert w["generated"] == {"encoder": True, "decoder": True}
    assert w["device"]["platform"] == "cpu"
    # same seed and config: the second call writes nothing
    assert chip_smoke.phase_weights(cfg)["generated"] == {"encoder": False, "decoder": False}

    chip_smoke.write_serving_config(cfg)
    # the server child gets ONE device, like the chip machine (this process
    # already initialised its own eight)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    s = chip_smoke.phase_serve(cfg, boot_timeout_s=600.0)
    assert s["device"] == {**s["device"], "platform": "cpu", "count": 1}
    assert set(s["model_boot_s"]) == {chip_smoke.EMB_MODEL, chip_smoke.CHAT_MODEL}
    assert s["prefix"]["prefix_hits"] >= 2 and s["prefix"]["kv_shared_pages"] >= 1
    assert s["json_attempts"][-1] == "parsed"

    r = chip_smoke.phase_rag(cfg)
    assert r["corpus_rows"] == 2000 and len(r["turns"]) == 3
    assert r["embed_tokenizer"] == "python:ByteTokenizer"
    assert chip_smoke._same_device([w, r]) == w["device"]


def test_smoke_multichip_rehearses_on_four_virtual_devices(tmp_path):
    """``--multichip``'s phase on four of the virtual CPU devices: one device
    vs a model=4 mesh, per-device projection bytes, 2 x TP-2 behind the router."""
    cfg = _tiny(tmp_path, decoder=_TP4_DECODER, chunk_size=512)
    m = chip_smoke.phase_multichip(cfg, new_tokens=4)
    assert m["device"]["count"] >= 4
    assert all(0.2 <= v <= 0.4 for v in m["projection_share_per_device"].values())
    assert m["compare"]["long"]["prompt_tokens"] >= 260
    assert m["compare"]["long"]["logits_rel_rms"] < 1e-3  # float32 on the CPU
    a, b = m["replica_devices"]
    assert len(a) == len(b) == 2 and not set(a) & set(b)


def test_multichip_catches_replicated_weights(tmp_path, monkeypatch):
    """The fault the byte check exists for: every device holding everything."""
    import jax

    from django_assistant_bot_tpu import parallel

    def replicate(params, logical, mesh, *a, **kw):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(params, NamedSharding(mesh, P()))

    monkeypatch.setattr(parallel, "shard_pytree", replicate)
    cfg = _tiny(tmp_path, decoder=_TP4_DECODER, chunk_size=512)
    with pytest.raises(chip_smoke.SmokeFailure, match="layer-projection bytes"):
        chip_smoke.phase_multichip(cfg, new_tokens=2)


def test_device_gate_refuses_the_cpu(tmp_path):
    cfg = _tiny(tmp_path, require_tpu=True)
    with pytest.raises(chip_smoke.SmokeFailure, match="not a TPU"):
        chip_smoke.phase_weights(cfg)
    assert not os.path.exists(cfg.work_dir)  # refused before any weight was drawn
    chip_smoke._check_device(cfg, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    with pytest.raises(chip_smoke.SmokeFailure, match="need 4"):
        chip_smoke._check_device(cfg, {"platform": "tpu", "kind": "x", "count": 1}, need=4)
    assert chip_smoke.full_config().require_tpu
    assert chip_smoke.full_config().decoder == chip_smoke.MISTRAL_7B_V01


@pytest.mark.parametrize("args", [[], ["--multichip"]])
def test_chip_smoke_main_fails_fast_off_the_chip(args):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero in seconds,
    prints no result, and never says ``"ok": true``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert time.monotonic() - t0 < 30
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert '"ok": true' not in p.stdout + p.stderr
    assert "not a TPU" in p.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program is not a pass."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_final_line_shape():
    line = json.dumps({"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}})
    assert line == '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}'
