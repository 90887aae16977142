"""``ccl_devinfo`` analogue — query platforms and devices.

Usage:
    PYTHONPATH=src python -m repro.cli.devinfo [--all] [--custom KEY ...]
"""

from __future__ import annotations

import argparse
import sys

from ..core import ErrBox, available_platforms

DEFAULT_KEYS = ["NAME", "PLATFORM", "KIND", "ID", "PROCESS_INDEX"]
TARGET_KEYS = ["PEAK_BF16_FLOPS", "HBM_BANDWIDTH", "HBM_BYTES",
               "ICI_LINK_BANDWIDTH", "ICI_LINKS", "VMEM_BYTES", "MXU_DIM",
               "VPU_SHAPE"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="repro device info")
    ap.add_argument("--all", action="store_true",
                    help="include the chip's published peaks (core/hw.py; "
                         "n/a for a device kind the table lacks)")
    ap.add_argument("--custom", nargs="*", default=None,
                    help="custom query: specific info keys only")
    args = ap.parse_args(argv)

    for plat in available_platforms():
        print(f"Platform: {plat.get_info('NAME')}  "
              f"(vendor={plat.get_info('VENDOR')}, "
              f"version={plat.get_info('VERSION')}, "
              f"devices={plat.get_info('NUM_DEVICES')})")
        for dev in plat.devices():
            keys = args.custom or (
                DEFAULT_KEYS + (TARGET_KEYS if args.all else []))
            print(f"  Device {dev.get_info('ID')}:")
            for k in keys:
                box = ErrBox()
                val = dev.get_info(k, err=box)
                print(f"    {k:22s} = {'n/a' if box.set else val}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
