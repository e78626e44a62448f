"""Host seconds of ``lgb.Dataset(...).construct()``: bin mappers and binning."""


def read(run):
    return run["phases"].get("ingest_s")
