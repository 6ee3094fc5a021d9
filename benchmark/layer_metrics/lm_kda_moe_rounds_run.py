"""`lm_moe_rounds_run` under the channel-gated family's name: the
rounds that the expert layer ran, worst layer of a step (the program's
counter `moe.rounds_run`, averaged over the window's log records). This
cell's chip holds 8 experts of 256, so a round is sized for 1/32 of the
assignments and a busier expert fills it sooner than in the cells that
hold 16."""

from benchmark.layer_metrics.lm_moe_rounds_run import read  # noqa: F401
