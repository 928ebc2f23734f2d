"""The share of the attention kernels' grid steps that the mask took out,
in %: of the score blocks of every flash forward (`flash.plan`: k_steps)
and backward (`flash.bwd_plan`: steps, all the outer loop's trips) that the
step's lowering plans, those wholly above the causal diagonal
(`skipped_causal`) or wholly older than the window (`skipped_window`),
which cost neither a fetch nor a matmul (kind train).  Read from the spans
of one abstract lowering of the step (benchmark/harness/lowered_spans.py),
a site as often as the lowering traces it.  None where the program's spans
have no such counts, as before PR 38."""

from benchmark.harness import lowered_spans

COUNTS = {"flash.plan": "k_steps", "flash.bwd_plan": "steps"}


def read(obs):
    steps = skipped = 0
    for name, sites in lowered_spans.of_step(obs, list(COUNTS)).items():
        for args in sites:
            if "skipped_window" not in args or args.get("engine") == "xla":
                continue
            steps += args[COUNTS[name]]
            skipped += args["skipped_causal"] + args["skipped_window"]
    return 100.0 * skipped / steps if steps else None
