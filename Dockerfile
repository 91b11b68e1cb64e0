# swarm-tpu worker image (parity with the reference's Dockerfile, which
# ships a CUDA torch base + ffmpeg and bind-mounts the HF cache;
# /root/reference Dockerfile:1-43). TPU differences: the base carries
# jax[tpu] instead of torch+cu118, libtpu comes from the TPU VM runtime,
# and the native artifact codec builds at image build time.

FROM python:3.12-slim-bookworm

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ zlib1g-dev libgl1 libglib2.0-0 ffmpeg \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/swarm-tpu
COPY pyproject.toml ./
COPY chiaswarm_tpu ./chiaswarm_tpu
COPY csrc ./csrc

# deps come from pyproject.toml; the [tpu] extra resolves libtpu for TPU
# VMs (on other hosts the base jax wheel's CPU backend runs)
RUN pip install --no-cache-dir -e ".[tpu]" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

# pre-build the native artifact codec (chiaswarm_tpu/native builds it on
# first use otherwise)
RUN python -c "from chiaswarm_tpu import native; assert native.load()"

# config + model cache live outside the image, like the reference's
# HF-cache bind mount (Dockerfile:28-37)
ENV SDAAS_ROOT=/data
VOLUME /data

ENTRYPOINT ["python", "-m", "chiaswarm_tpu.cli"]
CMD ["worker"]
