# Container image for the TPU worker.
#
# Parity with the reference image (Dockerfile:1-42): slim Python base,
# non-root user, curl healthcheck against /health, env-driven config — but
# the process model differs by design: ONE process per TPU chip/slice (the
# engine owns the device), concurrency via the continuous-batching
# scheduler, replicas scaled at the pod level (SURVEY §2.3). Expected to run
# on a TPU VM image / node pool where libtpu is provided by the host.

FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends curl \
    && rm -rf /var/lib/apt/lists/*

# jax[tpu] resolves libtpu on TPU VMs.
# tokenizers reads a checkpoint's tokenizer.json (models/tokenizer.py; transformers
# only for a directory without one); matplotlib: the wired plot tool; orbax:
# native checkpoints; the serve
# extras (confluent-kafka, pymongo, qdrant-client) are the reference-parity
# external backends.
RUN pip install --no-cache-dir "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
    && pip install --no-cache-dir safetensors tokenizers transformers matplotlib orbax-checkpoint \
       confluent-kafka pymongo qdrant-client

WORKDIR /app
COPY pyproject.toml ./
COPY finchat_tpu ./finchat_tpu
COPY prompts ./prompts

RUN useradd --create-home appuser && chown -R appuser /app
USER appuser

EXPOSE 8000
HEALTHCHECK --interval=30s --timeout=3s --start-period=60s --retries=3 \
    CMD curl -f http://localhost:8000/health || exit 1

CMD ["python", "-m", "finchat_tpu"]
