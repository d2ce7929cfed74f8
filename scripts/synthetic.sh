# Hermetic smoke run on an 8-virtual-device CPU mesh (no dataset needed)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python main.py \
  --dataset synthetic:2000:10:32:8 \
  --dropout 0.3 \
  --lr 0.01 \
  --n-partitions 4 \
  --n-epochs 60 \
  --n-layers 3 \
  --n-hidden 64 \
  --log-every 10 \
  --enable-pipeline \
  --use-pp
