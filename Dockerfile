# sentio-tpu serving image.
#
# Parity with the reference's Dockerfile (python slim, non-root, curl
# healthcheck, single server process) re-based for TPU hosts: the image is
# built FROM a JAX TPU base so libtpu and the TPU runtime are present, and
# the server binds the host's TPU devices (run with --privileged or the TPU
# device plugin on GKE). CPU-only dev: build with
#   docker build --build-arg BASE=python:3.12-slim .
# and the server falls back to jax[cpu] semantics (JAX_PLATFORMS=cpu).

ARG BASE=us-docker.pkg.dev/ml-images/jax/jax-tpu:latest
FROM ${BASE}

WORKDIR /app

# the JAX TPU base ships the jax stack; slim/CPU bases need the runtime deps
COPY requirements.txt ./
RUN python -c "import jax, aiohttp, httpx, einops, optax" 2>/dev/null \
    || pip install --no-cache-dir -r requirements.txt

COPY sentio_tpu/ sentio_tpu/
COPY prompts/ prompts/

# the C++ BM25 core builds on first use when a toolchain exists; bake it at
# image build time so runtime containers need no compiler
RUN python -c "from sentio_tpu import native; native.load_bm25()" || true

RUN useradd --create-home --uid 10001 sentio \
    && chown -R sentio:sentio /app
USER sentio

ENV PYTHONUNBUFFERED=1 \
    SENTIO_HOST=0.0.0.0 \
    SENTIO_PORT=8000

EXPOSE 8000
HEALTHCHECK --interval=30s --timeout=5s --start-period=120s --retries=3 \
    CMD python -c "import urllib.request,os; urllib.request.urlopen(f'http://127.0.0.1:{os.environ.get(\"SENTIO_PORT\",8000)}/health', timeout=4)"

CMD ["python", "-m", "sentio_tpu.cli", "serve"]
