# Pinned test/dev environment for tensor2robot_tpu.
# Reference parity: the reference shipped a docker/ + CI setup pinning
# its TF1 environment (SURVEY.md §3 last row); this is the jax-era
# equivalent. requirements.txt is the one installation — libtpu
# included — for the CPU suite (this image) and for TPU machines
# (`python chip_smoke.py`); only the environment below differs.
#
# Build:  docker build -t tensor2robot-tpu .
# Test:   docker run --rm tensor2robot-tpu
# Shell:  docker run --rm -it tensor2robot-tpu bash

FROM python:3.12-slim

ENV PIP_NO_CACHE_DIR=1 \
    PYTHONDONTWRITEBYTECODE=1 \
    # Tests run on a virtual 8-device CPU mesh (multi-chip sharding
    # without TPU hardware); conftest.py re-asserts these.
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    TF_CPP_MIN_LOG_LEVEL=2

WORKDIR /workspace

COPY requirements.txt .
RUN pip install -r requirements.txt

COPY tensor2robot_tpu/ tensor2robot_tpu/
COPY tests/ tests/
COPY chip_smoke.py __graft_entry__.py ./

CMD ["python", "-m", "pytest", "tests/", "-q"]
