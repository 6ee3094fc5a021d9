"""Pallas TPU kernels for the framework's hot ops."""

from tensor2robot_tpu.ops.cem_select import cem_select_lax
from tensor2robot_tpu.ops.cem_select import fused_cem_select
from tensor2robot_tpu.ops.flash_attention import flash_attention
