"""launch.trigger_serve: the thin CLI over the serving loop — ticks
submitted to a ServingLoop over the fault-tolerant engine, warm-up left
out of the report, every tick answered in order."""

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.core.interaction_net import forward_sr, init
from repro.launch import trigger_serve
from repro.serving import ResilientEngine, ServingLoop
from repro.serving.trigger import make_stream, serve_ticks


def _counter(out, name):
    """A counter's value in the --health report (0 when not printed)."""
    line = next(ln for ln in out.splitlines() if "counters:" in ln)
    for item in line.split()[1:]:
        key, _, value = item.partition("=")
        if key == name:
            return int(value)
    return 0


def test_cli_stream_excludes_warmup_ticks(capsys):
    """The warm-up ticks compile and are served, then the metrics are
    reset: the report and its counters hold the other ticks only."""
    rc = trigger_serve.main(["--forward", "sr", "--batch", "8",
                             "--batches", "5", "--warmup", "2", "--health"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ticks      3 after 2 warm-up: served=3 shed=0" in out
    assert "(24 events /" in out
    assert _counter(out, "loop_requests") == 3
    assert _counter(out, "rows_valid") == 24
    assert _counter(out, "compiles") == 0      # warm-up compiled it all


def test_cli_stream_answers_every_tick_in_order():
    """The driver drops and reorders nothing: ticks that straddle plan
    cuts (5 events into buckets of 8) come back whole, each the
    reference logits of its own tick."""
    cfg = get_arch("jedinet-30p").model
    params = init(jax.random.PRNGKey(0), cfg)
    engine = ResilientEngine(params, cfg, forward="sr", max_batch=8,
                             mesh=None)
    ticks = make_stream(np.random.RandomState(1), 7, 5, cfg.n_objects,
                        cfg.n_features)
    futures = serve_ticks(ServingLoop(engine), ticks)
    assert [f.rid for f in futures] == list(range(7))
    for fut, tick in zip(futures, ticks):
        assert fut.done and not fut.shed
        ref = np.asarray(forward_sr(params, cfg, tick))
        assert np.abs(fut.result() - ref).max() < 1e-5
    assert engine.metrics.counter("loop_completed") == 7


def test_cli_stream_single_tick_no_warmup(capsys):
    """A one-tick stream with no warm-up is measured: the tick is served
    over a positive wall time, so the sustained rate is finite."""
    rc = trigger_serve.main(["--forward", "sr", "--batch", "8",
                             "--batches", "1", "--warmup", "0", "--health"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ticks      1 after 0 warm-up: served=1 shed=0" in out
    kgps = float(out.split("sustained", 1)[1].split()[0])
    wall = float(out.split("(8 events / ", 1)[1].split()[0])
    assert np.isfinite(kgps) and wall > 0
    assert _counter(out, "rows_valid") == 8


def test_cli_drill_deadline_sheds_expired_ticks(capsys):
    """A drill slows every dispatch (latency seam) and so holds the loop
    while the rest of a tick waits in the batcher past its serve-by
    budget: the engine sheds it, the ticks dispatched in time are
    served, and the report counts both."""
    rc = trigger_serve.main(["--forward", "sr", "--batch", "12",
                             "--batches", "4", "--deadline-ms", "100",
                             "--drill", "latency:99:0.5", "--health"])
    out = capsys.readouterr().out
    assert rc == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("  ticks"))
    served = int(line.split("served=")[1].split()[0])
    shed = int(line.split("shed=")[1].split()[0])
    assert served + shed == 4 and shed >= 1 and served >= 1
    assert _counter(out, "shed_requests") >= 1
    assert "[health] state=shedding" in out


def test_cli_serves_through_serving_loop(capsys):
    """Every tick is one request of the serving loop, and every one of
    them completes."""
    rc = trigger_serve.main(["--forward", "sr", "--batch", "8",
                             "--batches", "6", "--warmup", "0", "--health"])
    out = capsys.readouterr().out
    assert rc == 0
    assert _counter(out, "loop_requests") == 6
    assert _counter(out, "loop_completed") == 6
    assert _counter(out, "loop_plans") >= 1


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
    assert "ticks      4 after 1 warm-up" in out


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
    # the per-plan row counters of the four measured ticks; the
    # compile-cache misses fell in the warm-up, which is left out
    for counter in ("rows_valid=32", "rows_bucket=32", "rows_kernel=32"):
        assert counter in out, counter
    assert "compiles=" not in out


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
    step evaluates counted (each unordered pair once: 128 x 128 / 2 lanes
    per jet)."""
    rc = trigger_serve.main(["--arch", "part-jetclass", "--forward", "part",
                             "--batch", "8", "--batches", "3",
                             "--warmup", "1", "--health"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "[trigger_serve] arch=part-jetclass" in out
    assert "forward=part n_objects=128" in out
    assert "path=part (chain part>part_xla)" in out
    assert f"pairs_kernel={2 * 8 * 128 * 128 // 2}" in out
    assert "level=none" in out


def test_cli_list_paths_resolves_the_part_policy(capsys):
    trigger_serve.main(["--arch", "part-jetclass", "--list-paths",
                        "--batch", "1024"])
    out = capsys.readouterr().out
    policy = out.split("bucket policy", 1)[1]
    assert "part paths" in policy
    for path, per_jet in (("part", "1474560"), ("part_xla", "8912896")):
        row = next(ln for ln in policy.splitlines()
                   if ln.startswith(path + " "))
        assert row.split()[1] == per_jet        # HBM bytes per jet
        assert row.split()[-1] == "8,16,32,64,128,256,512,1024"
    assert "fused_full" not in policy
