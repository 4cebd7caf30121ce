"""Record the small trace the trace-reduction tests read.

    python3 chipbench/tests/record_trace.py <out_dir>      # on a TPU

Runs the harness on a 2-layer, 256-wide Qwen2-shaped model (data/small.json)
under data/small-mix.json for a 3-second traced window, and copies the
``.xplane.pb`` to ``<out_dir>/small.xplane.pb`` with the reduction's
summary beside it (``small.reduced.json``).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T0 = time.monotonic()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from chipbench import harness, trace_reduce  # noqa: E402
from chipbench.spec import Cell, load_cell, load_json, load_traffic  # noqa: E402


def main(out_dir: str) -> int:
    bench = load_cell("qwen1.5-0.5b.industrial3p-over.sat")
    cell = Cell("small", 1, load_json(HERE / "data" / "small.json"),
                load_traffic("small-mix", HERE / "data"), bench.end_to_end,
                bench.per_layer)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        res = harness.run(cell, 3, 3.0, True, T0, trace_dir=tmp)
        path = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
        shutil.copy(path, out / "small.xplane.pb")
    red = trace_reduce.reduce(trace_reduce.load(str(out / "small.xplane.pb")))
    summary = {k: red[k] for k in ("window_s", "busy_s", "modules", "ops")}
    summary["n_gaps"] = len(red["gaps"])
    summary["result"] = res
    (out / "small.reduced.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary)[:4000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
