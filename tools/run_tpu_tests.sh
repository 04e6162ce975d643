#!/bin/sh
# The @pytest.mark.tpu kernel tests (Mosaic-compiled Pallas, hardware PRNG) on
# the real chip, from the repo root:
#
#     chiprun -- tools/run_tpu_tests.sh
#
# tests/conftest.py only DEFAULTS JAX_PLATFORMS to cpu, so exporting tpu here
# is what points the five files at the chip (and makes a chipless host fail
# loudly instead of skipping everything). One pytest process: the chip belongs
# to one process at a time.
set -e
cd "$(dirname "$0")/.."
JAX_PLATFORMS=tpu exec python -m pytest \
    tests/test_flash_tpu.py tests/test_flash_pair.py \
    tests/test_fused_residual.py tests/test_dropout_pallas.py \
    tests/test_paged_decode_tpu.py \
    -q -p no:cacheprovider "$@"
