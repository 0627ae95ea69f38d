"""launch.trigger_serve: the double-buffered serve_stream loop edge cases
and the thin-CLI-over-engine entry point."""

import jax
import numpy as np

from repro.launch import trigger_serve
from repro.launch.trigger_serve import make_stream, serve_stream
from repro.serving import ServingMetrics


def _identity_fwd():
    """A jitted async-dispatch stand-in for a forward path."""
    return jax.jit(lambda x: x * 2.0)


def _stream(n_batches, batch=4):
    return [np.full((batch, 3), float(i), np.float32)
            for i in range(n_batches)]


def test_serve_stream_warmup_longer_than_stream_is_empty_stats():
    """warmup >= stream length: every batch is warmup — empty stats, no
    crash, and the degenerate wall stays 0 (callers print 'too short')."""
    for n in (0, 1, 2):
        lat, events, wall = serve_stream(_identity_fwd(), _stream(n),
                                         warmup=2)
        assert lat == []
        assert events == 0
        if n == 0:
            assert wall == 0.0


def test_serve_stream_excludes_warmup_from_accounting():
    fwd = _identity_fwd()
    lat, events, wall = serve_stream(fwd, _stream(7, batch=5), warmup=2)
    assert len(lat) == 5                  # 7 batches - 2 warmup
    assert events == 5 * 5                # KGPS accounting skips warmup rows
    assert wall > 0
    assert all(t > 0 for t in lat)


def test_serve_stream_single_batch_stream():
    """The prefetch loop must handle a 1-batch stream: the primed transfer
    is the only batch, and with warmup=0 it is measured — including a
    positive wall time so KGPS stays finite."""
    fwd = _identity_fwd()
    lat, events, wall = serve_stream(fwd, _stream(1, batch=3), warmup=0)
    assert len(lat) == 1
    assert events == 3
    assert wall > 0.0


def test_serve_stream_records_into_metrics():
    m = ServingMetrics()
    serve_stream(_identity_fwd(), _stream(6, batch=4), warmup=2,
                 metrics=m, bucket=8)
    snap = m.snapshot()
    assert snap["batches"] == 4
    assert snap["events"] == 16
    assert snap["buckets"] == [8]


def test_serve_stream_computes_through_the_pipeline():
    """Double buffering must not drop or reorder batches."""
    fwd = _identity_fwd()
    stream = _stream(4, batch=2)
    outs = []
    orig = jax.device_put

    def capture(x):
        d = orig(x)
        outs.append(np.asarray(x)[0, 0])
        return d

    jax.device_put, saved = capture, jax.device_put
    try:
        serve_stream(fwd, stream, warmup=0)
    finally:
        jax.device_put = saved
    assert outs == [0.0, 1.0, 2.0, 3.0]


def test_make_stream_shapes():
    rng = np.random.RandomState(0)
    stream = make_stream(rng, 3, batch=6, n_objects=8, n_features=16)
    assert len(stream) == 3
    assert all(b.shape == (6, 8, 16) and b.dtype == np.float32
               for b in stream)


def test_cli_main_reports_stats_through_engine(capsys):
    trigger_serve.main(["--forward", "sr", "--batch", "8", "--batches", "5", "--warmup", "1"])
    out = capsys.readouterr().out
    assert "sustained" in out and "KGPS" in out
    assert "p50" in out and "p99" in out
    assert "roofline" in out and "level=none" in out


def test_cli_main_short_stream_prints_hint(capsys):
    trigger_serve.main(["--forward", "sr", "--batch", "4", "--batches", "2"])
    out = capsys.readouterr().out
    assert "too short" in out


def test_cli_main_fused_full_interpret(capsys):
    """The acceptance path, shrunk: fused_full through the engine on CPU."""
    trigger_serve.main(["--forward", "fused_full", "--interpret",
                        "--batch", "4", "--batches", "4",
                        "--warmup", "1"])
    out = capsys.readouterr().out
    assert "KGPS" in out and "level=full" in out


def test_cli_list_paths_prints_fallback_chains_and_policy(capsys):
    """--list-paths is the operator's view of the degradation ladder:
    the registry table must carry each path's fallback chain next to
    its resolved bucket policy."""
    trigger_serve.main(["--list-paths", "--batch", "16"])
    out = capsys.readouterr().out
    assert "fallback chain" in out
    assert "fused_full>sr_split" in out      # int8 path's two-rung chain
    assert "bucket policy" in out


def test_cli_health_flag_reports_state(capsys):
    trigger_serve.main(["--forward", "sr", "--batch", "8", "--batches", "5", "--warmup", "1",
                        "--health"])
    out = capsys.readouterr().out
    assert "[health] state=healthy" in out
    assert "chain=sr" in out
    assert "path=sr" in out                  # serving line + bucket detail
    # the per-plan row counters and the compile-cache misses
    for counter in ("rows_valid=32", "rows_bucket=32", "rows_kernel=32",
                    "compiles="):
        assert counter in out, counter


def test_cli_reports_serving_path_and_chain(capsys):
    rc = trigger_serve.main(["--forward", "fused_full", "--interpret",
                        "--batch", "4", "--batches", "4",
                        "--warmup", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "platform=cpu interpret=True" in out
    assert "path=fused_full" in out and "chain fused_full>sr_split" in out


def test_cli_fails_when_the_requested_path_does_not_serve(capsys,
                                                          monkeypatch):
    """Outside --drill the ladder still serves the stream, but a stream
    served by a fallback rung is a failed run: exit code 1, and the
    rung's error is printed."""
    import dataclasses

    from repro.core import paths

    def refused(params, cfg, x, *, interpret=False):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setitem(paths._REGISTRY, "fused_full", dataclasses.replace(
        paths.get("fused_full"), forward=refused))
    rc = trigger_serve.main(["--forward", "fused_full", "--interpret",
                             "--batch", "4", "--batches", "3",
                             "--warmup", "1"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "served by 'sr_split'" in out
    assert "kernel refused by the compiler" in out


def test_compile_cache_dir_is_fixed_per_checkout(monkeypatch, tmp_path):
    """The cache lives at <checkout>/.jax_cache (gitignored) unless
    JAX_COMPILATION_CACHE_DIR is set — then JAX's own setting applies
    and the code sets nothing."""
    import pathlib

    from repro.common.compile_cache import setup_compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert setup_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cli_serves_part_jetclass(capsys):
    """ParT at its published widths through the same CLI and engine: a
    short stream of 8-jet ticks on the ``part`` path (its pair embedding
    the ``part_pair_embed`` kernel, interpreted here), with the pairs the
    step evaluates counted (128 x 128 per jet)."""
    rc = trigger_serve.main(["--arch", "part-jetclass", "--forward", "part",
                             "--batch", "8", "--batches", "3",
                             "--warmup", "1", "--health"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "[trigger_serve] arch=part-jetclass" in out
    assert "forward=part n_objects=128" in out
    assert "path=part (chain part>part_xla)" in out
    assert f"pairs_kernel={2 * 8 * 128 * 128}" in out
    assert "level=none" in out


def test_cli_list_paths_resolves_the_part_policy(capsys):
    trigger_serve.main(["--arch", "part-jetclass", "--list-paths",
                        "--batch", "1024"])
    out = capsys.readouterr().out
    policy = out.split("bucket policy", 1)[1]
    assert "part paths" in policy
    for path, per_jet in (("part", "1376256"), ("part_xla", "8912896")):
        row = next(ln for ln in policy.splitlines()
                   if ln.startswith(path + " "))
        assert row.split()[1] == per_jet        # HBM bytes per jet
        assert row.split()[-1] == "8,16,32,64,128,256,512,1024"
    assert "fused_full" not in policy
