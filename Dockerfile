# llms-on-kubernetes-tpu serving image.
#
# The reference pulled prebuilt engine images (vllm/vllm-openai,
# quay.io/ramalama — reference values.yaml:21-24 both charts); this
# framework's engine is in-repo, so the image recipe lives here too.
#
#   CPU / local (ramalama-equivalent):   docker build -t llms-on-kubernetes-tpu .
#   TPU (GKE v5e/v5p node pools):        docker build --build-arg JAX_EXTRA=tpu -t llms-on-kubernetes-tpu:tpu .
#
# The same image serves both chart paths: `serve` (engine) and `router`
# (python gateway); the native router/loader binaries are built in the
# builder stage and included.

FROM python:3.12-slim AS native-builder
RUN apt-get update && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*
COPY native /src/native
RUN make -C /src/native/router && make -C /src/native/loader

FROM python:3.12-slim
ARG JAX_EXTRA=cpu
WORKDIR /app
COPY pyproject.toml /app/
COPY llms_on_kubernetes_tpu /app/llms_on_kubernetes_tpu
RUN pip install --no-cache-dir "jax[${JAX_EXTRA}]==0.9.0" \
    && pip install --no-cache-dir ".[serve,hf]"
COPY --from=native-builder /src/native/router/llkt-router /usr/local/bin/
COPY --from=native-builder /src/native/loader/libstload.so /app/native/loader/
ENV LLMK_NATIVE_LOADER_PATH=/app/native/loader/libstload.so
# the charts mount the HF cache PVC here (reference model-deployments.yaml:45-47)
VOLUME /root/.cache/huggingface
EXPOSE 8080
ENTRYPOINT ["python", "-m", "llms_on_kubernetes_tpu"]
CMD ["serve", "--help"]
