"""train_rows_per_s: epochs x corpus rows x jobs completed in the window,
over the window's wall time on the host clock.  A job is plan() then
execute() from w = 0, so this is the inverse of a user's time to a trained
model per row and epoch."""


def read(rec):
    done = [j for j in rec.jobs if not j.error]
    if not done:
        return None
    rows = rec.cell.config["corpus"]["rows"]
    return rec.epochs * rows * len(done) / rec.elapsed_s
