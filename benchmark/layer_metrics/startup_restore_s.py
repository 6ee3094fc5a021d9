"""Seconds the restore of the checkpoint the run resumes from took (the
span `startup.restore`: on a thread of its own beside the compile and
input phases under `train_eval_model`, on the trainer's thread under
`train_qtopt`), from the gauge `startup.restore_s`; 0 for a run that
resumed nothing. None where the program sets no such gauge."""


def read(run):
  from tensor2robot_tpu import telemetry

  return telemetry.registry().scalars("startup.").get(
      "startup.restore_s")
