import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
# interpret the flash kernels off the TPU, as chip_smoke's rehearsal does
os.environ.setdefault("ACCELERATE_TPU_FLASH", "1")
