from repro.utils.segments import (  # noqa: F401
    boundaries_from_keys,
    cummax,
    rank_in_segment,
    segment_ids_from_boundaries,
    segment_length_at_start,
    segment_start,
)
