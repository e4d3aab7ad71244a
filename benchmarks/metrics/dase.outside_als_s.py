"""Host seconds of one run_train outside ops.als.train_als (read, prepare,
placement, serialise, checksum, persist), averaged over the window's trains.
Source: the harness's spans around run_train and train_als."""


def read(record):
    whole = record.window_span_seconds("run_train")
    inner = record.window_span_seconds("train_als")
    if not whole or len(inner) != len(whole):
        return None
    return (sum(whole) - sum(inner)) / len(whole)
