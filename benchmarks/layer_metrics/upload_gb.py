"""Scan and upload (columnar/device.batch_to_device): GB (1e9 bytes) placed
on the device since the process started, the program's counter
``tpu_upload_bytes_total`` at the end of the run: every lane, validity
included.  The table is uploaded once and pinned, so a change that loses
the pin cache reads a multiple.  Nothing to read in a program that has no
such counter."""

COUNTER = "tpu_upload_bytes_total"


def read(run):
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == COUNTER:
            return family.total() / 1e9
    return None
