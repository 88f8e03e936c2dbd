"""step.mfu.pretrain (%): layer "whole step", moves pretrain_imgs_per_s.

Operations that forward and backward of two views need per image
(``flops_per_image`` of the file that the configuration names under ``flops``:
convolutions, dense head, NT-Xent; nothing recomputed) times the images per second of the traced steady stretch (whole
steps on the device's timeline, summed over the chips), over chips times the
chip's peak. Source: device trace."""


def read(run):
    if not run.get("stretches"):
        return None
    t0, t1, steps = run["stretches"][run["worst"]]
    imgs_per_s = steps * run["global_batch"] / ((t1 - t0) / 1e9)
    per_image = run["flops"].flops_per_image(
        run["config"]["model"], run["size"], run["global_batch"],
        run["config"]["architecture"]["head_out_dim"])
    return 100.0 * per_image * imgs_per_s / (run["chips"] * run["peaks"]["flops_per_s"])
