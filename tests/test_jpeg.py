"""JPEG-family codec: roundtrip, partial decoding, split decode."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from conftest import smooth_image
from repro.preprocessing import dct, jpeg


@pytest.mark.parametrize("quality", [50, 75, 95, 100])
@pytest.mark.parametrize("subsample", [False, True])
def test_roundtrip_quality(quality, subsample, rng):
    img = smooth_image(rng, 120, 150)
    out = jpeg.decode(jpeg.encode(img, quality=quality, subsample=subsample))
    assert out.shape == img.shape
    mae = np.abs(out.astype(int) - img.astype(int)).mean()
    assert mae < (8.0 if quality < 90 else 2.5)


def test_q100_near_lossless(rng):
    img = smooth_image(rng, 64, 64)
    out = jpeg.decode(jpeg.encode(img, quality=100))
    assert np.abs(out.astype(int) - img.astype(int)).max() <= 2


def test_grayscale(rng):
    img = smooth_image(rng, 72, 80)[..., 0]
    out = jpeg.decode(jpeg.encode(img, quality=90))
    assert out.shape == img.shape


def test_compression_ratio_ordering(rng):
    img = np.clip(
        smooth_image(rng, 128, 128).astype(int) + rng.integers(-12, 12, (128, 128, 3)),
        0,
        255,
    ).astype(np.uint8)
    sizes = {q: len(jpeg.encode(img, quality=q)) for q in (50, 75, 95)}
    assert sizes[50] <= sizes[75] <= sizes[95]
    assert img.size / sizes[75] > 3  # meaningfully compressed


@settings(max_examples=15, deadline=None)
@given(
    y0=st.integers(0, 60),
    x0=st.integers(0, 80),
    hh=st.integers(8, 60),
    ww=st.integers(8, 60),
    data=st.data(),
)
def test_roi_decode_matches_full(y0, x0, hh, ww, data):
    rng = np.random.default_rng(42)
    img = smooth_image(rng, 128, 160)
    blob = jpeg.encode(img, quality=90)
    full = jpeg.decode(blob)
    y1, x1 = min(128, y0 + hh), min(160, x0 + ww)
    crop = jpeg.decode(blob, roi=(y0, x0, y1, x1))
    # snap outward to the 8px block grid, as Algorithm 1 does
    sy0, sx0 = (y0 // 8) * 8, (x0 // 8) * 8
    sy1 = min(128, ((y1 + 7) // 8) * 8)
    sx1 = min(160, ((x1 + 7) // 8) * 8)
    assert np.array_equal(crop, full[sy0:sy1, sx0:sx1])


def test_early_stop_matches_top_rows(rng):
    img = smooth_image(rng, 128, 96)
    blob = jpeg.encode(img, quality=85)
    full = jpeg.decode(blob)
    for rows in (8, 40, 64, 128):
        assert np.array_equal(jpeg.decode(blob, max_rows=rows), full[:rows])


def test_dc_only_progressive(rng):
    img = smooth_image(rng, 128, 96)
    blob = jpeg.encode(img, quality=85)
    dc = jpeg.decode(blob, dc_only=True)
    assert dc.shape == (16, 12, 3)
    # the DC image is the 8x8 block means, approximately
    ref = img.reshape(16, 8, 12, 8, 3).mean(axis=(1, 3))
    assert np.abs(dc.astype(float) - ref).mean() < 12


def test_split_decode_equals_full(rng):
    """Host entropy stage + (separately applied) dequant+IDCT must equal
    the one-shot decoder: the placement split is semantics-preserving."""
    img = smooth_image(rng, 64, 64)
    blob = jpeg.encode(img, quality=90)
    hdr, planes_zz, qtables, _ = jpeg.decode_to_coefficients(blob)
    recon = [jpeg._idct_plane(zz, qt) + 128.0 for zz, qt in zip(planes_zz, qtables)]
    ycc = np.stack(recon, axis=-1)
    rgb = np.clip(np.round(dct.ycbcr_to_rgb(ycc)), 0, 255).astype(np.uint8)
    assert np.array_equal(rgb[:64, :64], jpeg.decode(blob))


@pytest.mark.parametrize("subsample", [False, True])
@pytest.mark.parametrize("hw", [(96, 128), (97, 131)])
def test_decode_scaled_factor1_equals_full(rng, subsample, hw):
    img = smooth_image(rng, *hw)
    blob = jpeg.encode(img, quality=88, subsample=subsample)
    assert np.array_equal(jpeg.decode_scaled(blob, 1), jpeg.decode(blob))


@pytest.mark.parametrize("factor", [2, 4])
@pytest.mark.parametrize("subsample", [False, True])
def test_decode_scaled_tracks_downsampled_full(rng, factor, subsample):
    # reduced-resolution decode approximates the area-downsampled full
    # decode (bandlimited reconstruction; close on piecewise-smooth input)
    h, w = 160, 224
    img = smooth_image(rng, h, w)
    blob = jpeg.encode(img, quality=92, subsample=subsample)
    scaled = jpeg.decode_scaled(blob, factor)
    assert scaled.shape == (h // factor, w // factor, 3)
    full = jpeg.decode(blob).astype(np.float64)
    ds = full.reshape(h // factor, factor, w // factor, factor, 3).mean(axis=(1, 3))
    assert np.abs(scaled.astype(np.float64) - ds).mean() < 3.0


def test_decode_scaled_grayscale_and_odd_sizes(rng):
    img = smooth_image(rng, 101, 67)[..., 0]
    blob = jpeg.encode(img, quality=85)
    out = jpeg.decode_scaled(blob, 2)
    assert out.shape == (51, 34)  # ceil(101/2), ceil(67/2)
    assert out.ndim == 2
    with pytest.raises(ValueError, match="factor"):
        jpeg.decode_scaled(blob, 3)


def _encoder_coefficients(img, subsample):
    """The quantised zigzag planes the encoder codes, (rows, cols, 64) each."""
    if img.ndim == 2:
        planes = [img.astype(np.float64)]
    else:
        ycc = dct.rgb_to_ycbcr(img)
        planes = [ycc[..., 0]]
        for c in (1, 2):
            p = ycc[..., c]
            if subsample:
                p = np.pad(p, ((0, p.shape[0] % 2), (0, p.shape[1] % 2)), mode="edge")
                p = p.reshape(p.shape[0] // 2, 2, p.shape[1] // 2, 2).mean(axis=(1, 3))
            planes.append(p)
    qtables = jpeg._qtables(90, len(planes))
    out = []
    for plane, qt in zip(planes, qtables):
        zz, n_br, n_bc = jpeg._quantize_plane(plane - 128.0, qt)
        out.append(zz.reshape(n_br, n_bc, 64))
    return out


_COEFF_CASES = [
    (layout, band_rows, region, False)
    for layout in ("420", "444", "gray")
    for band_rows in (3, 4, 5)
    for region in ("full", "roi", "max_rows")
] + [("420", 3, "full", True), ("420", 4, "roi", True)]


@pytest.mark.parametrize("layout,band_rows,region,stored", _COEFF_CASES)
def test_decode_to_coefficients_returns_encoder_coefficients(
    monkeypatch, layout, band_rows, region, stored
):
    # 100x90 at 4:2:0 has a 7-row chroma grid; an odd band_rows makes
    # adjacent bands share a chroma block row, which must appear once
    from repro.preprocessing import compression

    rng = np.random.default_rng(7)
    img = smooth_image(rng, 100, 90)
    img = np.clip(img + rng.integers(-20, 21, img.shape), 0, 255).astype(np.uint8)
    if layout == "gray":
        img = img[..., 0]
    subsample = layout == "420"
    with monkeypatch.context() as m:
        if stored:
            m.setattr(compression, "_zstd", None)
        blob = jpeg.encode(img, quality=90, subsample=subsample, band_rows=band_rows)
    hdr = jpeg.peek_header(blob)
    if stored:
        assert blob[hdr.payload_start] == compression.STORED

    kw = {"full": {}, "roi": {"roi": (40, 8, 72, 50)}, "max_rows": {"max_rows": 45}}[region]
    hdr, planes_zz, _, row_ranges = jpeg.decode_to_coefficients(blob, **kw)
    expected = _encoder_coefficients(img, subsample)
    assert len(planes_zz) == len(expected) == len(row_ranges)
    grids = [(hdr.n_br, hdr.n_bc)] + [jpeg.chroma_grid(hdr)] * (len(planes_zz) - 1)
    for zz, want, (r0, r1), grid in zip(planes_zz, expected, row_ranges, grids):
        assert want.shape[:2] == grid
        assert zz.dtype == np.int16 and zz.shape == (r1 - r0, grid[1], 64)
        np.testing.assert_array_equal(zz, want[r0:r1])
    lo, hi = row_ranges[0]
    if region == "full":
        assert all(r == (0, g[0]) for r, g in zip(row_ranges, grids))
    elif region == "roi":  # whole bands covering pixel rows 40..72
        assert lo * 8 <= 40 and hi * 8 >= 72 and lo % band_rows == 0
    else:
        assert lo == 0 and 6 <= hi < 6 + band_rows


@pytest.mark.parametrize("subsample", [False, True])
def test_stage_coefficients_layouts_roundtrip(rng, subsample):
    # both staging layouts carry the same blocks; the padded layout's
    # chroma sits in the top-left corner of the luma grid, the packed
    # layout concatenates planes at native density
    img = smooth_image(rng, 97, 131)
    blob = jpeg.encode(img, quality=85, subsample=subsample)
    hdr, planes_zz, _, _ = jpeg.decode_to_coefficients(blob)
    cbr, cbc = jpeg.chroma_grid(hdr)
    padded = jpeg.stage_coefficients(planes_zz, hdr, "padded")
    packed = jpeg.stage_coefficients(planes_zz, hdr, "packed")
    assert padded.shape == jpeg.staged_coeff_shape(hdr, "padded")
    assert packed.shape == jpeg.staged_coeff_shape(hdr, "packed")
    assert padded.dtype == packed.dtype == np.int16
    np.testing.assert_array_equal(padded[0], planes_zz[0])
    np.testing.assert_array_equal(padded[1, :cbr, :cbc], planes_zz[1])
    if subsample:
        # padding region stays zero, and packed is strictly smaller
        assert not padded[1, cbr:].any() and not padded[1, :, cbc:].any()
        assert packed.nbytes < padded.nbytes
    n_luma = hdr.n_br * hdr.n_bc
    np.testing.assert_array_equal(
        packed[:n_luma].reshape(hdr.n_br, hdr.n_bc, 64), planes_zz[0]
    )
    np.testing.assert_array_equal(
        packed[n_luma : n_luma + cbr * cbc].reshape(cbr, cbc, 64), planes_zz[1]
    )
    with pytest.raises(ValueError, match="layout"):
        jpeg.staged_coeff_shape(hdr, "ragged")


def test_partial_decode_is_cheaper(rng):
    """ROI decoding must touch fewer bands (cost model depends on it)."""
    import time

    img = smooth_image(rng, 512, 512)
    blob = jpeg.encode(img, quality=85)
    t0 = time.perf_counter()
    for _ in range(3):
        jpeg.decode(blob)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
        jpeg.decode(blob, roi=(0, 0, 64, 64))
    t_roi = time.perf_counter() - t0
    assert t_roi < t_full * 0.7


# ------------------------------------------------------------- pjpeg (libjpeg)
def test_pjpeg_roundtrip_and_formats(rng):
    """The Pillow-backed codec: roundtrip fidelity + StoredImage plumbing."""
    from repro.preprocessing.formats import ImageFormat, StoredImage

    img = smooth_image(rng, 120, 150)
    fmt = ImageFormat("pjpeg", None, 95)
    stored = StoredImage.from_array(img, [fmt])
    out = stored.decode(fmt)
    assert out.shape == img.shape and out.dtype == np.uint8
    assert np.abs(out.astype(int) - img.astype(int)).mean() < 4.0


def test_pjpeg_scaled_decode_is_partial(rng):
    """short_side on pjpeg = decode-time scaled IDCT (stored stays native):
    the output covers the target short side at a 1/2^k scale and decoding
    it is cheaper than the full-resolution decode."""
    import time

    from repro.preprocessing.formats import ImageFormat, StoredImage

    img = smooth_image(rng, 512, 512)
    full = ImageFormat("pjpeg", None, 90)
    scaled = ImageFormat("pjpeg", 64, 90)
    stored = StoredImage.from_array(img, [full, scaled])
    # same stored bytes: short_side never creates a resized variant
    assert stored.nbytes(full) == stored.nbytes(scaled)
    out = stored.decode(scaled)
    assert min(out.shape[:2]) == 64  # 512 / 8, never undershooting 64
    assert stored.decode(full).shape == img.shape

    t0 = time.perf_counter()
    for _ in range(5):
        stored.decode(full)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(5):
        stored.decode(scaled)
    t_scaled = time.perf_counter() - t0
    assert t_scaled < t_full


def test_pjpeg_scaled_decode_roi_in_native_coords(rng):
    """roi stays in native full-resolution coordinates (the contract shared
    with jpeg.decode / planner.central_roi) even under scaled decode."""
    from repro.preprocessing.formats import ImageFormat, StoredImage

    img = smooth_image(rng, 512, 512)
    scaled = ImageFormat("pjpeg", 64, 90)
    stored = StoredImage.from_array(img, [scaled])
    out = stored.decode(scaled, roi=(128, 128, 384, 384))
    assert out.shape[:2] == (32, 32)  # a 256-px native window at 1/8 scale
    whole = stored.decode(scaled)
    np.testing.assert_array_equal(out, whole[16:48, 16:48])


def test_pjpeg_dc_only_matches_eighth_scale(rng):
    from repro.preprocessing.formats import ImageFormat, StoredImage

    img = smooth_image(rng, 256, 256)
    fmt = ImageFormat("pjpeg", None, 90)
    stored = StoredImage.from_array(img, [fmt])
    dc = stored.decode(fmt, dc_only=True)
    assert dc.shape[:2] == (32, 32)
