"""Pallas TPU kernels for the compute hot spots.

``ops`` dispatches to them with the kernel mode derived from the
platform: Mosaic-compiled on a TPU, the Pallas interpreter on CPU."""
from . import ops, ref
from .bitonic import (bitonic_sort, bitonic_sort_kv, merge_sorted_rows,
                      sort_sentinel)
from .bucketize import bucketize_histogram, searchsorted
from .flash_attention import flash_attention
from .radix import radix_sort, key_to_bits, bits_to_key

__all__ = ["ops", "ref", "bitonic_sort", "bitonic_sort_kv",
           "merge_sorted_rows", "sort_sentinel", "bucketize_histogram",
           "searchsorted", "flash_attention",
           "radix_sort", "key_to_bits", "bits_to_key"]
