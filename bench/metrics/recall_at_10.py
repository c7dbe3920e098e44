"""recall_at_10: mean recall@10 of every query answered, against the plain
reference's exact 10 nearest neighbours."""


def read(run):
    return float(run.recall.mean())
