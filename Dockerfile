# gatekeeper-tpu image: serves both the control-plane manager and the
# engine worker (deploy/gatekeeper-tpu.yaml runs the same image with
# different commands — reference analogue: /root/reference/Dockerfile,
# one binary image).
#
# The TPU runtime (libtpu) is installed with jax[tpu] below.  On a node
# without a TPU, jax runs on the CPU; a failed device probe is logged at
# error level and counted (device_probe_failures), and the engine serves
# from the scalar oracle.  `python chip_smoke.py` fails unless it finds a
# TPU.

FROM python:3.12-slim

# native toolchain for the columnar-ingest C extension (compiled on
# first import, gatekeeper_tpu/native/__init__.py) and openssl for the
# webhook's self-signed serving certs (webhook/bootstrap.py)
RUN apt-get update && apt-get install -y --no-install-recommends \
        gcc libc6-dev openssl && rm -rf /var/lib/apt/lists/*

# the versions chip_smoke.py passed with on a v5e chip (PR 21); a failed
# install fails the build
RUN pip install --no-cache-dir "jax[tpu]==0.9.0" "jaxlib==0.9.0" \
        "libtpu==0.0.34" -f \
        https://storage.googleapis.com/jax-releases/libtpu_releases.html
RUN pip install --no-cache-dir numpy pyyaml

WORKDIR /app
COPY gatekeeper_tpu /app/gatekeeper_tpu

# warm the native extension build at image build time
RUN python -c "from gatekeeper_tpu import native; print('native:', native.available)"

ENV PYTHONUNBUFFERED=1
EXPOSE 8443
ENTRYPOINT ["python", "-m", "gatekeeper_tpu.cmd.manager"]
