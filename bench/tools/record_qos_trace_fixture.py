"""Record the QoS profiler trace that tests/bench/test_bench_phases.py
reduces: the smoke-size QoS engine of tests/bench/conftest.py (qwen3
smoke configuration, decode TAF, the canary on a quarter of ticks)
serving one wave through `harness.serve_window` after the untimed wave,
so the trace holds the benchmark's spans, the engine's `engine.*` and
`tick.*` spans, both step programs and the device planes of a real run.

    python3 bench/tools/record_qos_trace_fixture.py OUT_DIR [--seconds S]

Run it on a chip; it copies the `.xplane.pb` to
OUT_DIR/tick_trace_qos.xplane.pb. The committed copy is
tests/bench/fixtures/qos/tick_trace_qos.xplane.pb, in a directory of its
own: `xplane.reduce_trace` of tests/bench/fixtures reads the last trace
file under it, which must stay tick_trace.xplane.pb.
"""
import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.join(ROOT, "tests", "bench")]

CELL = ("qwen3-smoke", "smoke-qos")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.05)
    args = ap.parse_args(argv)
    from conftest import make_bench_root
    from bench import cells, harness, traffic
    root = make_bench_root(tempfile.mkdtemp(), [CELL])
    conf = cells.load_config(root, CELL[0])
    mix = cells.load_traffic(root, CELL[1])
    cfg = cells.program_config(conf, approx=False)
    params = cells.family(conf).program_params(0, conf,
                                               cfg.padded_vocab_size)
    engine = harness.build_engine(root, conf, mix, params)
    engine.warmup()
    waves = traffic.waves(mix, 0, conf["vocab_size"])
    harness.warm_wave(engine, waves)
    tmp = tempfile.mkdtemp()
    win = harness.serve_window(engine, waves, args.seconds, trace_dir=tmp)
    os.makedirs(args.out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                    recursive=True)[0]
    dst = os.path.join(args.out, "tick_trace_qos.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    shutil.rmtree(root)
    print(dst, os.path.getsize(dst), win.counters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
