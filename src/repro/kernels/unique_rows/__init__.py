from repro.kernels.unique_rows.kernel import sorted_ranks_pallas
from repro.kernels.unique_rows.ops import unique_rows
from repro.kernels.unique_rows.ref import unique_rows_ref

__all__ = ["unique_rows", "sorted_ranks_pallas", "unique_rows_ref"]
