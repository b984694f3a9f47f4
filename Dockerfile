# TPU runtime image for distributed_llms_example_tpu.
#
# TPU-native counterpart of the reference's CUDA image (reference
# Dockerfile:1-27: nvidia/cuda:12.2.0 base + python3.9 + unpinned pip
# installs).  Differences on purpose: no GPU userspace at all — jax[tpu]
# ships libtpu and talks to the accelerator directly — versions are
# pinned, and g++ is included so the native JSONL loader
# (distributed_llms_example_tpu/native/) compiles on first use.
#
# Build:  docker build -t dllm-tpu:latest .
# The Valohai steps in valohai.yaml run this image on TPU VM hosts.

FROM python:3.12-slim-bookworm

# g++ for the native data loader; git for VCS-pinned installs if needed
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ git curl \
    && rm -rf /var/lib/apt/lists/*

# JAX with the TPU runtime (libtpu wheel comes from the jax release index),
# then the model/data/checkpoint stack.  Versions pinned to a known-good
# set; bump deliberately, together.
RUN pip install --no-cache-dir \
    "jax[tpu]==0.9.0" \
    -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
    && pip install --no-cache-dir \
    "flax==0.12.0" \
    "optax==0.2.6" \
    "orbax-checkpoint==0.11.28" \
    "chex==0.1.91" \
    "einops==0.8.1" \
    "numpy>=2.0" \
    "transformers==4.57.1" \
    "safetensors==0.6.2" \
    "sentencepiece==0.2.1" \
    "valohai-utils==0.7.0"

WORKDIR /workspace
COPY distributed_llms_example_tpu/ distributed_llms_example_tpu/
COPY valohai.yaml __graft_entry__.py _dllm_env.py dllm_test_bootstrap.py pyproject.toml ./

# pre-build the native JSONL loader so first use doesn't pay the compile
RUN python -c "from distributed_llms_example_tpu import native; assert native.available(), native.build_error()"

ENV PYTHONUNBUFFERED=1
CMD ["python", "-m", "distributed_llms_example_tpu.launch.cli", "--help"]
