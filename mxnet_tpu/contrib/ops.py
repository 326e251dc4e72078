"""Contrib operators: detection ops for SSD/RCNN.

Parity: reference ``src/operator/contrib/`` — MultiBoxPrior
(multibox_prior.cc), MultiBoxTarget (multibox_target.cc), MultiBoxDetection
(multibox_detection.cc), Proposal (proposal.cc), CTCLoss (the warpctc
plugin op), fft/ifft (fft.cc — cuFFT wrappers in the reference),
quantize/dequantize (quantize.cc), count_sketch (count_sketch.cc). These
are the ops the SSD and Faster-RCNN examples are built on (SURVEY.md §7
workload 4).

All are implemented as vectorized jnp — box overlap matrices batch onto
the VPU; no per-anchor loops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..ops.registry import OpDef, register
from ..ops.utils import as_tuple


# --------------------------------------------------------------------------
# MultiBoxPrior: anchor box generation
# --------------------------------------------------------------------------
def _parse_floats(v, default):
    if v is None:
        return list(default)
    if isinstance(v, (int, float)):
        return [float(v)]
    return [float(x) for x in v]


def _multibox_prior(attrs, ins, is_train):
    data = ins[0]
    sizes = _parse_floats(attrs.get("sizes"), (1.0,))
    ratios = _parse_floats(attrs.get("ratios"), (1.0,))
    steps = _parse_floats(attrs.get("steps"), (-1.0, -1.0))
    offsets = _parse_floats(attrs.get("offsets"), (0.5, 0.5))
    h, w = data.shape[2], data.shape[3]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if len(steps) > 1 and steps[1] > 0 else 1.0 / w
    num_anchors = len(sizes) + len(ratios) - 1
    cy = (jnp.arange(h) + offsets[0]) * step_y
    cx = (jnp.arange(w) + offsets[1]) * step_x
    cyg, cxg = jnp.meshgrid(cy, cx, indexing="ij")  # [h,w]
    ws, hs = [], []
    for i in range(num_anchors):
        if i < len(sizes):
            s = sizes[i]
            r = ratios[0]
        else:
            s = sizes[0]
            r = ratios[i - len(sizes) + 1]
        sr = np.sqrt(r)
        ws.append(s * sr / 2.0)
        hs.append(s / sr / 2.0)
    ws = jnp.asarray(ws)
    hs = jnp.asarray(hs)
    cxg = cxg[..., None]  # [h,w,1]
    cyg = cyg[..., None]
    boxes = jnp.stack(
        [
            cxg - ws, cyg - hs, cxg + ws, cyg + hs,
        ],
        axis=-1,
    )  # [h,w,A,4]
    return [boxes.reshape(1, -1, 4)]


def _multibox_prior_infer(attrs, in_shapes):
    d = in_shapes[0]
    sizes = _parse_floats(attrs.get("sizes"), (1.0,))
    ratios = _parse_floats(attrs.get("ratios"), (1.0,))
    num_anchors = len(sizes) + len(ratios) - 1
    return [tuple(d)], [(1, d[2] * d[3] * num_anchors, 4)], []


register(
    OpDef(
        "_contrib_MultiBoxPrior",
        _multibox_prior,
        arguments=("data",),
        defaults={"sizes": (1.0,), "ratios": (1.0,), "clip": False,
                  "steps": (-1.0, -1.0), "offsets": (0.5, 0.5)},
        infer_shape=_multibox_prior_infer,
        aliases=("MultiBoxPrior",),
    )
)


# --------------------------------------------------------------------------
# box IoU helper
# --------------------------------------------------------------------------
def _iou(boxes_a, boxes_b):
    """[Na,4] x [Nb,4] → [Na,Nb] IoU (corner format)."""
    ax1, ay1, ax2, ay2 = [boxes_a[:, i] for i in range(4)]
    bx1, by1, bx2, by2 = [boxes_b[:, i] for i in range(4)]
    ix1 = jnp.maximum(ax1[:, None], bx1[None, :])
    iy1 = jnp.maximum(ay1[:, None], by1[None, :])
    ix2 = jnp.minimum(ax2[:, None], bx2[None, :])
    iy2 = jnp.minimum(ay2[:, None], by2[None, :])
    iw = jnp.maximum(ix2 - ix1, 0.0)
    ih = jnp.maximum(iy2 - iy1, 0.0)
    inter = iw * ih
    area_a = jnp.maximum((ax2 - ax1) * (ay2 - ay1), 0.0)
    area_b = jnp.maximum((bx2 - bx1) * (by2 - by1), 0.0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


# --------------------------------------------------------------------------
# MultiBoxTarget: anchor → ground-truth matching + target encoding
# --------------------------------------------------------------------------
def _multibox_target(attrs, ins, is_train):
    anchors, labels, cls_preds = ins
    overlap_thresh = float(attrs.get("overlap_threshold", 0.5))
    negative_mining_ratio = float(attrs.get("negative_mining_ratio", -1.0))
    variances = _parse_floats(attrs.get("variances"), (0.1, 0.1, 0.2, 0.2))
    anc = anchors[0]  # [A,4]
    A = anc.shape[0]
    B = labels.shape[0]

    def one_sample(lab):
        # lab: [M, >=5] rows [cls, x1,y1,x2,y2]; cls<0 = invalid
        valid = lab[:, 0] >= 0
        gt = lab[:, 1:5]
        ious = _iou(anc, gt) * valid[None, :]  # [A,M]
        best_iou = jnp.max(ious, axis=1)
        best_gt = jnp.argmax(ious, axis=1)
        match = best_iou > overlap_thresh
        # also force-match the best anchor for each gt
        best_anchor = jnp.argmax(ious, axis=0)  # [M]
        force = jnp.zeros((A,), bool).at[best_anchor].set(valid)
        match = match | force
        cls_target = jnp.where(
            match, lab[best_gt, 0] + 1.0, 0.0
        )
        # encode location targets
        acx = (anc[:, 0] + anc[:, 2]) / 2
        acy = (anc[:, 1] + anc[:, 3]) / 2
        aw = jnp.maximum(anc[:, 2] - anc[:, 0], 1e-8)
        ah = jnp.maximum(anc[:, 3] - anc[:, 1], 1e-8)
        g = gt[best_gt]
        gcx = (g[:, 0] + g[:, 2]) / 2
        gcy = (g[:, 1] + g[:, 3]) / 2
        gw = jnp.maximum(g[:, 2] - g[:, 0], 1e-8)
        gh = jnp.maximum(g[:, 3] - g[:, 1], 1e-8)
        tx = (gcx - acx) / aw / variances[0]
        ty = (gcy - acy) / ah / variances[1]
        tw = jnp.log(gw / aw) / variances[2]
        th = jnp.log(gh / ah) / variances[3]
        loc_target = jnp.stack([tx, ty, tw, th], axis=-1)  # [A,4]
        loc_mask = match[:, None].astype(jnp.float32) * jnp.ones((1, 4))
        loc_target = loc_target * loc_mask
        return loc_target.reshape(-1), loc_mask.reshape(-1), cls_target

    loc_t, loc_m, cls_t = jax.vmap(one_sample)(labels)
    return [loc_t, loc_m, cls_t]


def _multibox_target_infer(attrs, in_shapes):
    anc, lab, cls = in_shapes
    A = anc[1]
    B = lab[0]
    return (
        [tuple(anc), tuple(lab), tuple(cls)],
        [(B, A * 4), (B, A * 4), (B, A)],
        [],
    )


register(
    OpDef(
        "_contrib_MultiBoxTarget",
        _multibox_target,
        arguments=("anchor", "label", "cls_pred"),
        outputs=("loc_target", "loc_mask", "cls_target"),
        defaults={
            "overlap_threshold": 0.5, "ignore_label": -1.0,
            "negative_mining_ratio": -1.0, "negative_mining_thresh": 0.5,
            "minimum_negative_samples": 0,
            "variances": (0.1, 0.1, 0.2, 0.2),
        },
        infer_shape=_multibox_target_infer,
        need_top_grad=False,
        aliases=("MultiBoxTarget",),
    )
)


# --------------------------------------------------------------------------
# MultiBoxDetection: decode + NMS
# --------------------------------------------------------------------------
def _multibox_detection(attrs, ins, is_train):
    cls_prob, loc_pred, anchors = ins
    threshold = float(attrs.get("threshold", 0.01))
    nms_threshold = float(attrs.get("nms_threshold", 0.5))
    nms_topk = int(attrs.get("nms_topk", -1))
    variances = _parse_floats(attrs.get("variances"), (0.1, 0.1, 0.2, 0.2))
    clip = bool(attrs.get("clip", True))
    anc = anchors[0]  # [A,4]
    A = anc.shape[0]
    B = cls_prob.shape[0]
    num_classes = cls_prob.shape[1]

    acx = (anc[:, 0] + anc[:, 2]) / 2
    acy = (anc[:, 1] + anc[:, 3]) / 2
    aw = anc[:, 2] - anc[:, 0]
    ah = anc[:, 3] - anc[:, 1]

    def one_sample(probs, locs):
        # probs [C,A], locs [A*4]
        locs = locs.reshape(A, 4)
        cx = locs[:, 0] * variances[0] * aw + acx
        cy = locs[:, 1] * variances[1] * ah + acy
        w = jnp.exp(locs[:, 2] * variances[2]) * aw / 2
        h = jnp.exp(locs[:, 3] * variances[3]) * ah / 2
        boxes = jnp.stack([cx - w, cy - h, cx + w, cy + h], axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        # class with max prob (excluding background class 0)
        fg = probs[1:]  # [C-1, A]
        cls_id = jnp.argmax(fg, axis=0).astype(jnp.float32)  # [A]
        score = jnp.max(fg, axis=0)
        keep = score > threshold
        cls_id = jnp.where(keep, cls_id, -1.0)
        # greedy NMS via iterative suppression (static A iterations capped)
        order = jnp.argsort(-score)
        ious = _iou(boxes, boxes)

        def body(i, state):
            suppressed, out_id = state
            idx = order[i]
            valid = (cls_id[idx] >= 0) & (~suppressed[idx])
            same_cls = cls_id == cls_id[idx]
            sup_new = suppressed | (
                valid & same_cls & (ious[idx] > nms_threshold) &
                (jnp.arange(A) != idx)
            )
            return sup_new, out_id

        suppressed = jnp.zeros((A,), bool)
        max_iter = A if nms_topk <= 0 else min(nms_topk, A)
        suppressed, _ = jax.lax.fori_loop(
            0, max_iter, body, (suppressed, 0)
        )
        final_id = jnp.where(suppressed, -1.0, cls_id)
        return jnp.stack(
            [final_id, score, boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]],
            axis=-1,
        )

    out = jax.vmap(one_sample)(cls_prob, loc_pred)
    return [out]


def _multibox_detection_infer(attrs, in_shapes):
    cls, loc, anc = in_shapes
    return (
        [tuple(cls), tuple(loc), tuple(anc)],
        [(cls[0], anc[1], 6)],
        [],
    )


register(
    OpDef(
        "_contrib_MultiBoxDetection",
        _multibox_detection,
        arguments=("cls_prob", "loc_pred", "anchor"),
        defaults={
            "clip": True, "threshold": 0.01, "background_id": 0,
            "nms_threshold": 0.5, "force_suppress": False,
            "variances": (0.1, 0.1, 0.2, 0.2), "nms_topk": -1,
        },
        infer_shape=_multibox_detection_infer,
        need_top_grad=False,
        aliases=("MultiBoxDetection",),
    )
)


# --------------------------------------------------------------------------
# Proposal (Faster R-CNN RPN proposals) — reference proposal.cc
# --------------------------------------------------------------------------
def _generate_base_anchors(base_size, scales, ratios):
    base = np.array([0, 0, base_size - 1, base_size - 1], np.float32)
    w = base[2] - base[0] + 1
    h = base[3] - base[1] + 1
    cx = base[0] + 0.5 * (w - 1)
    cy = base[1] + 0.5 * (h - 1)
    anchors = []
    for r in ratios:
        size = w * h
        size_ratio = size / r
        ws = int(round(np.sqrt(size_ratio)))
        hs = int(round(ws * r))
        for s in scales:
            wss = ws * s
            hss = hs * s
            anchors.append(
                [cx - 0.5 * (wss - 1), cy - 0.5 * (hss - 1),
                 cx + 0.5 * (wss - 1), cy + 0.5 * (hss - 1)]
            )
    return np.array(anchors, np.float32)


def _proposal(attrs, ins, is_train):
    cls_prob, bbox_pred, im_info = ins
    feature_stride = int(attrs.get("feature_stride", 16))
    scales = _parse_floats(attrs.get("scales"), (4.0, 8.0, 16.0, 32.0))
    ratios = _parse_floats(attrs.get("ratios"), (0.5, 1.0, 2.0))
    rpn_pre_nms_top_n = int(attrs.get("rpn_pre_nms_top_n", 6000))
    rpn_post_nms_top_n = int(attrs.get("rpn_post_nms_top_n", 300))
    nms_thresh = float(attrs.get("threshold", 0.7))
    min_size = float(attrs.get("rpn_min_size", 16))

    base_anchors = jnp.asarray(
        _generate_base_anchors(feature_stride, scales, ratios)
    )  # [A,4]
    A = base_anchors.shape[0]
    H, W = cls_prob.shape[2], cls_prob.shape[3]
    shift_x = jnp.arange(W) * feature_stride
    shift_y = jnp.arange(H) * feature_stride
    sy, sx = jnp.meshgrid(shift_y, shift_x, indexing="ij")
    shifts = jnp.stack(
        [sx.ravel(), sy.ravel(), sx.ravel(), sy.ravel()], axis=-1
    )  # [HW,4]
    anchors = (base_anchors[None] + shifts[:, None]).reshape(-1, 4)  # [HW*A,4]

    scores = cls_prob[0, A:].transpose(1, 2, 0).reshape(-1)  # fg scores
    deltas = bbox_pred[0].transpose(1, 2, 0).reshape(-1, 4)
    # decode
    widths = anchors[:, 2] - anchors[:, 0] + 1.0
    heights = anchors[:, 3] - anchors[:, 1] + 1.0
    ctr_x = anchors[:, 0] + 0.5 * (widths - 1.0)
    ctr_y = anchors[:, 1] + 0.5 * (heights - 1.0)
    pred_ctr_x = deltas[:, 0] * widths + ctr_x
    pred_ctr_y = deltas[:, 1] * heights + ctr_y
    pred_w = jnp.exp(deltas[:, 2]) * widths
    pred_h = jnp.exp(deltas[:, 3]) * heights
    boxes = jnp.stack(
        [
            pred_ctr_x - 0.5 * (pred_w - 1), pred_ctr_y - 0.5 * (pred_h - 1),
            pred_ctr_x + 0.5 * (pred_w - 1), pred_ctr_y + 0.5 * (pred_h - 1),
        ],
        axis=-1,
    )
    im_h, im_w = im_info[0, 0], im_info[0, 1]
    boxes = jnp.stack(
        [
            jnp.clip(boxes[:, 0], 0, im_w - 1),
            jnp.clip(boxes[:, 1], 0, im_h - 1),
            jnp.clip(boxes[:, 2], 0, im_w - 1),
            jnp.clip(boxes[:, 3], 0, im_h - 1),
        ],
        axis=-1,
    )
    ws = boxes[:, 2] - boxes[:, 0] + 1
    hs = boxes[:, 3] - boxes[:, 1] + 1
    valid = (ws >= min_size) & (hs >= min_size)
    scores = jnp.where(valid, scores, -1.0)

    k = min(rpn_pre_nms_top_n, scores.shape[0])
    top_scores, top_idx = jax.lax.top_k(scores, k)
    top_boxes = boxes[top_idx]
    ious = _iou(top_boxes, top_boxes)

    def body(i, suppressed):
        valid_i = (~suppressed[i]) & (top_scores[i] > 0)
        sup_new = suppressed | (
            valid_i & (ious[i] > nms_thresh) & (jnp.arange(k) > i)
        )
        return sup_new

    suppressed = jax.lax.fori_loop(0, k, body, jnp.zeros((k,), bool))
    keep_score = jnp.where(suppressed, -1.0, top_scores)
    n_out = min(rpn_post_nms_top_n, k)
    final_scores, final_idx = jax.lax.top_k(keep_score, n_out)
    final_boxes = top_boxes[final_idx]
    rois = jnp.concatenate(
        [jnp.zeros((n_out, 1)), final_boxes], axis=-1
    )  # [N,5] with batch index 0
    if bool(attrs.get("output_score", False)):
        return [rois, final_scores[:, None]]
    return [rois]


def _proposal_infer(attrs, in_shapes):
    rpn_post = int(attrs.get("rpn_post_nms_top_n", 300))
    pre = int(attrs.get("rpn_pre_nms_top_n", 6000))
    cls = in_shapes[0]
    A = None
    outs = [(min(rpn_post, pre), 5)]
    if bool(attrs.get("output_score", False)):
        outs.append((min(rpn_post, pre), 1))
    return [tuple(s) for s in in_shapes], outs, []


_proposal_def = OpDef(
    "_contrib_Proposal",
    _proposal,
    arguments=("cls_prob", "bbox_pred", "im_info"),
    defaults={
        "rpn_pre_nms_top_n": 6000, "rpn_post_nms_top_n": 300,
        "threshold": 0.7, "rpn_min_size": 16,
        "scales": (4.0, 8.0, 16.0, 32.0), "ratios": (0.5, 1.0, 2.0),
        "feature_stride": 16, "output_score": False, "iou_loss": False,
    },
    infer_shape=_proposal_infer,
    need_top_grad=False,
    aliases=("Proposal",),
)
_proposal_def.list_outputs = lambda attrs=None: (
    ["output", "score"] if (attrs or {}).get("output_score") else ["output"]
)
register(_proposal_def)


# --------------------------------------------------------------------------
# ROIPooling — reference roi_pooling.cc (a core op, registered here with
# the detection family)
# --------------------------------------------------------------------------
def _roi_pooling(attrs, ins, is_train):
    data, rois = ins
    pooled_h, pooled_w = as_tuple(attrs["pooled_size"], 2, "pooled_size")
    spatial_scale = float(attrs.get("spatial_scale", 1.0))
    N, C, H, W = data.shape
    R = rois.shape[0]

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        roi_w = jnp.maximum(x2 - x1 + 1, 1)
        roi_h = jnp.maximum(y2 - y1 + 1, 1)
        img = data[batch_idx]  # [C,H,W]

        ys = jnp.arange(H)
        xs = jnp.arange(W)

        def pool_cell(ph, pw):
            hstart = y1 + (ph * roi_h) // pooled_h
            hend = y1 + ((ph + 1) * roi_h + pooled_h - 1) // pooled_h
            wstart = x1 + (pw * roi_w) // pooled_w
            wend = x1 + ((pw + 1) * roi_w + pooled_w - 1) // pooled_w
            mask = (
                (ys[:, None] >= hstart) & (ys[:, None] < hend)
                & (xs[None, :] >= wstart) & (xs[None, :] < wend)
            )
            masked = jnp.where(mask[None], img, -jnp.inf)
            val = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(val), val, 0.0)

        cells = jax.vmap(
            lambda ph: jax.vmap(lambda pw: pool_cell(ph, pw))(
                jnp.arange(pooled_w)
            )
        )(jnp.arange(pooled_h))  # [ph,pw,C]
        return cells.transpose(2, 0, 1)  # [C,ph,pw]

    out = jax.vmap(one_roi)(rois)
    return [out]


def _roi_pooling_infer(attrs, in_shapes):
    d, r = in_shapes
    ph, pw = as_tuple(attrs["pooled_size"], 2, "pooled_size")
    return [tuple(d), tuple(r)], [(r[0], d[1], ph, pw)], []


register(
    OpDef(
        "ROIPooling",
        _roi_pooling,
        arguments=("data", "rois"),
        defaults={"pooled_size": (7, 7), "spatial_scale": 1.0},
        infer_shape=_roi_pooling_infer,
    )
)


# --------------------------------------------------------------------------
# CTCLoss — reference plugin/warpctc (the 0.9.5-era CTC op; later versions
# moved it to src/operator/contrib/ctc_loss). Standard log-space
# forward-algorithm over the blank-extended label sequence; JAX autodiff
# through the lax.scan recursion yields the exact CTC gradient that the
# reference computes with warp-ctc's hand-written backward.
# Conventions (warp-ctc): blank label = 0; label entries are in
# [1, alphabet), 0-entries in the label matrix are padding.
# --------------------------------------------------------------------------
def _ctc_loss(attrs, ins, is_train):
    data, label = ins  # [T, B, C] activations (unnormalized), [B, L] labels
    t_len, b, c = data.shape
    l_max = label.shape[1]
    s = 2 * l_max + 1  # blank-extended length

    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)  # [T,B,C]
    label = label.astype(jnp.int32)
    # labels outside [0, alphabet) cannot raise under jit; gathers below are
    # clamped so they can't poison other samples with NaN, and the affected
    # sample's loss is forced to +inf — loud and deterministic in training
    # logs instead of a silent NaN cascade.
    oob_sample = jnp.any((label < 0) | (label >= c), axis=1)  # [B]
    label = jnp.clip(label, 0, c - 1)
    neg_inf = jnp.float32(-1e30)

    # extended sequence l'[b]: blank, l1, blank, l2, ... blank
    ext = jnp.zeros((b, s), jnp.int32)
    ext = ext.at[:, 1::2].set(label)  # [B, S]
    label_len = jnp.sum((label > 0).astype(jnp.int32), axis=1)  # [B]
    ext_len = 2 * label_len + 1

    # allow skip (s-2 -> s) where ext[s] != blank and ext[s] != ext[s-2]
    ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)))[:, :s]
    can_skip = (ext != 0) & (ext != ext_prev2)  # [B, S]

    pos = jnp.arange(s)[None, :]  # [1, S]
    valid = pos < ext_len[:, None]  # [B, S] states inside this label's lattice

    def emit(lp_t):
        # lp_t [B, C] -> per-state emission log-prob [B, S]
        return jnp.take_along_axis(lp_t, ext, axis=1)

    alpha0 = jnp.full((b, s), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, 0])
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(label_len > 0, jnp.take_along_axis(
            logp[0], label[:, :1], axis=1)[:, 0], neg_inf)
    )
    alpha0 = jnp.where(valid, alpha0, neg_inf)

    def step(alpha, lp_t):
        a_prev1 = jnp.pad(alpha, ((0, 0), (1, 0)), constant_values=neg_inf)[:, :s]
        a_prev2 = jnp.pad(alpha, ((0, 0), (2, 0)), constant_values=neg_inf)[:, :s]
        a_prev2 = jnp.where(can_skip, a_prev2, neg_inf)
        stacked = jnp.stack([alpha, a_prev1, a_prev2], axis=0)
        merged = jax.nn.logsumexp(stacked, axis=0)
        alpha_t = merged + emit(lp_t)
        alpha_t = jnp.where(valid, alpha_t, neg_inf)
        return alpha_t, None

    alpha_last, _ = jax.lax.scan(step, alpha0, logp[1:])

    # final states: ext_len-1 (last blank) and ext_len-2 (last symbol)
    idx_last = jnp.clip(ext_len - 1, 0, s - 1)
    idx_prev = jnp.clip(ext_len - 2, 0, s - 1)
    a_last = jnp.take_along_axis(alpha_last, idx_last[:, None], axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha_last, idx_prev[:, None], axis=1)[:, 0]
    a_prev = jnp.where(label_len > 0, a_prev, neg_inf)
    loss = -jax.nn.logsumexp(jnp.stack([a_last, a_prev]), axis=0)
    loss = jnp.where(oob_sample, jnp.float32(jnp.inf), loss)
    return [loss.astype(data.dtype)]


def _ctc_loss_infer(attrs, in_shapes):
    dshape, lshape = in_shapes
    if dshape is None:
        raise MXNetError("CTCLoss: data shape required")
    if len(dshape) != 3:
        raise MXNetError("CTCLoss: data must be [seq_len, batch, alphabet]")
    if lshape is None:
        raise MXNetError("CTCLoss: label shape required")
    return [tuple(dshape), tuple(lshape)], [(dshape[1],)], []


register(
    OpDef(
        "CTCLoss",
        _ctc_loss,
        arguments=("data", "label"),
        infer_shape=_ctc_loss_infer,
        aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"),
    )
)


# --------------------------------------------------------------------------
# fft / ifft — reference src/operator/contrib/fft.cc (cuFFT C2C). Layout
# parity: output interleaves real/imag along the last axis
# [re0, im0, re1, im1, ...]; ifft is UNNORMALIZED like cuFFT (round-trip
# ifft(fft(x)) == x * n), which the reference tests divide out by hand.
# --------------------------------------------------------------------------
def _fft(attrs, ins, is_train):
    x = ins[0]
    spec = jnp.fft.fft(x.astype(jnp.complex64), axis=-1)
    out = jnp.stack([spec.real, spec.imag], axis=-1)  # [..., d, 2]
    return [out.reshape(x.shape[:-1] + (2 * x.shape[-1],)).astype(jnp.float32)]


def _ifft(attrs, ins, is_train):
    x = ins[0]
    d = x.shape[-1] // 2
    inter = x.reshape(x.shape[:-1] + (d, 2)).astype(jnp.float32)
    spec = jax.lax.complex(inter[..., 0], inter[..., 1])
    # cuFFT inverse is unnormalized: scale back up by d
    out = jnp.fft.ifft(spec, axis=-1).real * d
    return [out.astype(jnp.float32)]


register(
    OpDef(
        "fft",
        _fft,
        arguments=("data",),
        defaults={"compute_size": 128},
        infer_shape=lambda attrs, ins: (
            [tuple(ins[0])],
            [tuple(ins[0][:-1]) + (2 * ins[0][-1],)],
            [],
        ),
        aliases=("_contrib_fft",),
    )
)
register(
    OpDef(
        "ifft",
        _ifft,
        arguments=("data",),
        defaults={"compute_size": 128},
        infer_shape=lambda attrs, ins: (
            [tuple(ins[0])],
            [tuple(ins[0][:-1]) + (ins[0][-1] // 2,)],
            [],
        ),
        aliases=("_contrib_ifft",),
    )
)


# --------------------------------------------------------------------------
# quantize / dequantize — reference src/operator/contrib/quantize.cc:
# affine-map [min_range, max_range] onto the uint8 range and back. On TPU
# this is the host-side calibration path; actual low-precision matmuls go
# through bf16/int8 XLA dots instead.
# --------------------------------------------------------------------------
def _quantize(attrs, ins, is_train):
    data, min_r, max_r = ins
    lo = jnp.min(min_r)
    hi = jnp.max(max_r)
    scale = 255.0 / jnp.maximum(hi - lo, 1e-8)
    q = jnp.clip(jnp.round((data - lo) * scale), 0, 255).astype(jnp.uint8)
    return [q, lo.reshape(1), hi.reshape(1)]


def _dequantize(attrs, ins, is_train):
    data, min_r, max_r = ins
    lo = jnp.min(min_r)
    hi = jnp.max(max_r)
    scale = jnp.maximum(hi - lo, 1e-8) / 255.0
    return [data.astype(jnp.float32) * scale + lo]


def _quantize_infer(attrs, in_shapes):
    d = in_shapes[0]
    return [tuple(d), (1,), (1,)], [tuple(d), (1,), (1,)], []


register(
    OpDef(
        "quantize",
        _quantize,
        arguments=("data", "min_range", "max_range"),
        outputs=("output", "min_output", "max_output"),
        infer_shape=_quantize_infer,
        infer_type=lambda attrs, in_types: (
            [np.float32, np.float32, np.float32],
            [np.uint8, np.float32, np.float32],
            [],
        ),
        aliases=("_contrib_quantize",),
    )
)
register(
    OpDef(
        "dequantize",
        _dequantize,
        arguments=("data", "min_range", "max_range"),
        infer_shape=lambda attrs, ins: (
            [tuple(ins[0]), (1,), (1,)],
            [tuple(ins[0])],
            [],
        ),
        infer_type=lambda attrs, in_types: (
            [np.uint8, np.float32, np.float32],
            [np.float32],
            [],
        ),
        aliases=("_contrib_dequantize",),
    )
)


# --------------------------------------------------------------------------
# count_sketch — reference src/operator/contrib/count_sketch.cc (compact
# bilinear pooling). out[n, h[i]] += s[i] * data[n, i]; expressed as one
# XLA scatter-add, whose transpose (gather) gives the backward pass the
# reference hand-codes.
# --------------------------------------------------------------------------
def _count_sketch_dim(attrs):
    out_dim = int(attrs.get("out_dim", 0))
    if out_dim <= 0:
        raise MXNetError("count_sketch: out_dim is required and must be > 0")
    return out_dim


def _count_sketch(attrs, ins, is_train):
    data, h, sgn = ins
    out_dim = _count_sketch_dim(attrs)
    idx = h.reshape(-1).astype(jnp.int32)  # [in_dim]
    signs = sgn.reshape(-1).astype(data.dtype)
    out = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    return [out.at[..., idx].add(data * signs)]


def _count_sketch_infer(attrs, in_shapes):
    d = in_shapes[0]
    out_dim = _count_sketch_dim(attrs)
    in_dim = d[-1]
    return (
        [tuple(d), (1, in_dim), (1, in_dim)],
        [tuple(d[:-1]) + (out_dim,)],
        [],
    )


register(
    OpDef(
        "count_sketch",
        _count_sketch,
        arguments=("data", "h", "s"),
        defaults={"out_dim": 0, "processing_batch_size": 32},
        infer_shape=_count_sketch_infer,
        aliases=("_contrib_count_sketch",),
    )
)


# --------------------------------------------------------------------------
# SwitchMoE: top-1 mixture-of-experts FFN as a Symbol op
# --------------------------------------------------------------------------
def _switch_moe(attrs, ins, is_train):
    """Expose parallel/moe.py's Switch-MoE through the Symbol/Module API
    (beyond-reference capability, SURVEY §2.3 expert-parallel row). Two
    outputs: the routed FFN result and the scalar-ish [1] load-balance
    aux loss (add it to the training objective via MakeLoss)."""
    from ..parallel.moe import switch_moe

    data, gate_w, w_up, w_down = ins
    y, aux = switch_moe(
        {"gate_w": gate_w, "w_up": w_up, "w_down": w_down},
        data,
        capacity_factor=float(attrs.get("capacity_factor", 1.25)),
    )
    return [y, aux.reshape(1)]


def _switch_moe_infer(attrs, in_shapes):
    data, gate, up, down = in_shapes
    if data is None:
        raise MXNetError("SwitchMoE: data shape required")  # resolvable later
    if len(data) != 2:
        # ValueError: a known-but-wrong rank is a hard contract violation
        # that must survive the infer fixpoint loop (like num_hidden below)
        raise ValueError("SwitchMoE: data must be [tokens, d_model] "
                         "(Reshape (B,T,D) inputs to (B*T, D))")
    d_model = data[1]
    num_experts = int(attrs["num_experts"])
    d_hidden = int(attrs["num_hidden"])
    if d_hidden <= 0:
        # ValueError (not MXNetError) so the message survives the infer
        # fixpoint loop, which treats MXNetError as "not resolvable yet"
        # — a 0 width would otherwise silently infer empty expert
        # weights and train the MoE branch as a no-op
        raise ValueError("SwitchMoE: num_hidden must be set (> 0)")
    return (
        [tuple(data), (d_model, num_experts),
         (num_experts, d_model, d_hidden), (num_experts, d_hidden, d_model)],
        [tuple(data), (1,)],
        [],
    )


register(
    OpDef(
        "_contrib_SwitchMoE",
        _switch_moe,
        arguments=("data", "gate_weight", "up_weight", "down_weight"),
        outputs=("output", "aux_loss"),
        defaults={"num_experts": 8, "num_hidden": 0,
                  "capacity_factor": 1.25},
        infer_shape=_switch_moe_infer,
        aliases=("SwitchMoE",),
    )
)


def contrib_op_exports():
    """What contrib/{symbol,ndarray}.py expose: ``X`` for every op registered
    as ``_contrib_X`` with the alias ``X`` (exported by being registered so),
    and the eight registered under the reference's other conventions."""
    from ..ops import registry

    return sorted({"ROIPooling", "CTCLoss", "ctc_loss", "fft", "ifft",
                   "quantize", "dequantize", "count_sketch"}.union(
        op.name[len("_contrib_"):] for op in registry.primary_ops()
        if op.name.startswith("_contrib_")
        and op.name[len("_contrib_"):] in op.aliases))
