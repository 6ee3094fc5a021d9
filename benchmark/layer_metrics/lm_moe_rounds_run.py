"""The rounds that the expert layer ran, worst layer of a step: the
program's counter `moe.rounds_run` (`parallel/moe.held_experts_ffn`,
through the model's step metrics), averaged over the loop's log records
inside the window. A round is a gather, three grouped matrix products
and a scatter-add over twice the uniform share of the assignments, so 1
is what even routing costs; at 2 a step has grown by a round a layer.
`None` where the program has no such counter."""


def read(run):
  rounds = [rec["moe.rounds_run"] for rec in run["records"]
            if "moe.rounds_run" in rec]
  return sum(rounds) / len(rounds) if rounds else None
