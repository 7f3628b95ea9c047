# TPU-native serving image (the analog of the reference's
# vllm/vllm-openai base + flashinfer pip layer, /root/reference/Dockerfile:1-6
# — there the CUDA engine comes from the base image; here the engine IS this
# repo, so the image is just python + jax[tpu] + the package).
#
# Build on a TPU VM (libtpu comes from the jax[tpu] extra):
#   docker build -t vllm-distributed-tpu .
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        curl ca-certificates \
    && rm -rf /var/lib/apt/lists/*

# jax[tpu] pins jaxlib+libtpu to matching versions; -f pulls libtpu wheels.
RUN pip install --no-cache-dir "jax[tpu]" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

WORKDIR /srv/vllm-distributed-tpu
COPY pyproject.toml ./
COPY vllm_distributed_tpu ./vllm_distributed_tpu
RUN pip install --no-cache-dir .

# XLA persistent compile cache lives on the cache volume
# (docker-compose.yml mounts ${ROOT_CACHE_PATH} -> /root/.cache, the same
# contract as the reference's compiled-model volume, docker-compose.yml:24-25).
ENV JAX_COMPILATION_CACHE_DIR=/root/.cache/vdt-xla

ENTRYPOINT ["python3", "-m", "vllm_distributed_tpu"]
