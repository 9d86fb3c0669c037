from repro.roofline.analysis import (  # noqa: F401
    DEVICE_PEAKS,
    DRYRUN_DEVICE_KIND,
    CollectiveStats,
    DevicePeaks,
    Roofline,
    model_flops_for,
    parse_collectives,
    peaks_for,
    shape_bytes,
)
