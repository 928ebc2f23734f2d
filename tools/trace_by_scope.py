"""Device ms a traced step by a coarse cut of each operation's scope path,
from the newest trace a benchmark run left under bench_out/trace (or under
the directory given): what two traces of one cell are diffed by, scope by
scope, when a step moved by more than the scope a PR changed.

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1
    python3 tools/trace_by_scope.py [bench_out/trace] > scopes.json

A key is `fwd:` / `bwd:` (under a layer's `recurrence` / `recurrence_grad`)
or `top:` (outside every layer) and the first two components of the path
inside the layer (`kda.mix/kda_conv_decay`, `matmul/dot_general:`), numbered
twins merged; `top:` alone holds the operations that carry no scope at all
(copies, converts, transposes).  A `while` or `conditional` holds others and
is left out; a step is what most operation names occur once in.  Reads
benchmark/harness/scope_time.py and trace.py, as the per-layer readers do;
run by no benchmark cell.
"""
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import scope_time, trace  # noqa: E402


def main() -> int:
    found = scope_time.newest(sys.argv[1] if len(sys.argv) > 1 else None)
    if found is None or not found[1] or trace.window(found[0]) is None:
        print("trace_by_scope: no device trace with a window there",
              file=sys.stderr)
        return 2
    prof, scopes = found
    t0, t1 = trace.window(prof)
    paths = scopes[min(scopes)]
    by_key, by_op = collections.Counter(), collections.Counter()
    n_key, n_name = collections.Counter(), collections.Counter()
    for name, s, e in trace.first_device_ops(prof, t0, t1):
        if re.match(r"(while|conditional)(\.\d+)?$", name):
            continue
        path = paths.get(name, "")
        n_name[name] += 1
        if "closed_call" in path:
            side = "bwd:" if "recurrence_grad" in path else "fwd:"
            cut = re.sub(r"^.*?closed_call/(checkpoint/)?", "", path)
        else:
            side, cut = "top:", re.sub(r"^jit\(fn\)/", "", path)
        key = side + "/".join([p for p in cut.split("/") if p][:2])
        key = re.sub(r"_\d+\b", "", key)
        by_key[key] += (e - s) / 1e6
        n_key[key] += 1
        by_op[re.sub(r"\.\d+$", "", name)] += (e - s) / 1e6
    steps = collections.Counter(n_name.values()).most_common(1)[0][0]
    print(json.dumps({
        "window_ms": (t1 - t0) / 1e6, "steps": steps,
        "by_scope_ms_a_step": {
            k: [round(v / steps, 3), round(n_key[k] / steps, 2)]
            for k, v in by_key.most_common(80)},
        "by_op_ms_a_step": {k: round(v / steps, 3)
                            for k, v in by_op.most_common(40)}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
