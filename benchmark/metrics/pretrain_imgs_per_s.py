"""pretrain_imgs_per_s (imgs/s, end to end, host clock): every image of every
step of the window, summed over the chips, over the whole window's time, from
the window's first dispatch to ``block_until_ready`` on the last state. Epoch
boundaries, flushes and drains are inside; nothing is dropped or medianed."""


def read(run):
    return run["images"] / run["window_s"]
