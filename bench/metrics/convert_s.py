"""Host seconds of ``formats.csr_to_spc5``, from the benchmark's own span
around the call (layer: format conversion)."""


def read(run):
    return run.layer.get("convert_s")
