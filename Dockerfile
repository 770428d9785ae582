# Test/deployment image for predictionio_tpu (role of the reference's
# Dockerfile test image). CPU-only: see JAX_PLATFORMS below.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make curl \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/pio
COPY pyproject.toml README.md ./
COPY predictionio_tpu ./predictionio_tpu
COPY bin ./bin
COPY conf ./conf
COPY tests ./tests
COPY docs ./docs

RUN pip install --no-cache-dir -e .[test] jax

# JAX_PLATFORMS=cpu: this image has no accelerator, and compute commands
# (and `pio status`) refuse to fall back to the CPU unless asked to
# (predictionio_tpu/utils/accelerator.py); override on a TPU host
ENV PIO_HOME=/opt/pio \
    PIO_FS_BASEDIR=/var/lib/pio_store \
    JAX_PLATFORMS=cpu \
    PATH="/opt/pio/bin:${PATH}"

EXPOSE 7070 8000 9000 7071
# default: verify the environment; override with eventserver/train/deploy
CMD ["pio", "status"]
