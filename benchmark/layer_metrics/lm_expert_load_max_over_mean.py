"""The busiest held expert's assignments over the held experts' mean,
worst layer of a step: the program's counter
`moe.expert_load_max_over_mean` (`parallel/moe.held_experts_ffn`, through
the model's step metrics), averaged over the loop's log records inside
the window. 1 is an even load; the grouped matrix product's time
follows the sum, the exchange of an expert-parallel pod the largest."""


def read(run):
  loads = [rec["moe.expert_load_max_over_mean"] for rec in run["records"]
           if "moe.expert_load_max_over_mean" in rec]
  return sum(loads) / len(loads) if loads else None
