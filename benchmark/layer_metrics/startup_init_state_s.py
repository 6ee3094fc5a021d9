"""Seconds the trainer took to initialise its state, derive its
shardings and place it on the device (the span `startup.init_state` on
the trainer's thread; in `train_qtopt` with the replay buffer's
construction before it), from the gauge `startup.init_state_s`. None
where the program sets no such gauge."""


def read(run):
  from tensor2robot_tpu import telemetry

  return telemetry.registry().scalars("startup.").get(
      "startup.init_state_s")
