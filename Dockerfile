# fishnet-tpu container image (reference: Dockerfile:1-10 — builder + slim
# runtime; here the "build" step compiles the native chesscore library and
# pre-trains/verifies assets instead of compiling engines).
FROM python:3.12-slim AS builder
RUN apt-get update && apt-get install -y --no-install-recommends g++ && \
    rm -rf /var/lib/apt/lists/*
WORKDIR /app
COPY fishnet_tpu ./fishnet_tpu
COPY bench.py __graft_entry__.py ./
RUN pip install --no-cache-dir "jax[cpu]" flax optax numpy && \
    g++ -O2 -std=c++17 -shared -fPIC fishnet_tpu/cc/chesscore.cpp \
        -o fishnet_tpu/cc/libchesscore.so

FROM python:3.12-slim
RUN useradd --create-home fishnet
WORKDIR /app
COPY --from=builder /usr/local/lib/python3.12/site-packages /usr/local/lib/python3.12/site-packages
COPY --from=builder /app /app
COPY docker-entrypoint.sh /docker-entrypoint.sh
RUN chmod +x /docker-entrypoint.sh
USER fishnet
ENV PYTHONPATH=/app
# this image installs jax[cpu]: the device path runs on XLA:CPU, and says
# so (--backend tpu refuses a CPU backend it was not asked for). A TPU
# image installs jax[tpu] and drops this line.
ENV JAX_PLATFORMS=cpu
# /app is read-only for the fishnet user; keep compiled programs at home
ENV JAX_COMPILATION_CACHE_DIR=/home/fishnet/.cache/fishnet-tpu/xla
ENTRYPOINT ["/docker-entrypoint.sh"]
