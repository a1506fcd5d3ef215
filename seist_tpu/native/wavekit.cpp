// wavekit — native host-side kernels for the input pipeline.
//
// The loader's per-sample cost is dominated by many small numpy ops with
// Python dispatch overhead (normalize + several soft-label placements per
// sample; ref training/preprocess.py:224-242,567-619). These C++ kernels do
// the same math in one call each; seist_tpu/native/__init__.py binds them
// via ctypes and seist_tpu/data/preprocess.py uses them when built
// (numerically equal to the numpy path within fp32 accumulation tolerance —
// verified by tests/test_native.py).
//
// Built by seist_tpu/native/__init__.py on first import (g++ -O3, no
// dependencies).

#include <cmath>
#include <cstdint>

extern "C" {

// Per-channel demean + scale. mode: 0 = std, 1 = max (SIGNED max, matching
// the reference's np.max at preprocess.py:228 — not abs-max), 2 = demean
// only. data is (C, L) float32, modified in place; zero std/max divides by
// 1 (reference's `[denom == 0] = 1` guard).
void znorm_f32(float* data, int64_t channels, int64_t length, int mode) {
  for (int64_t c = 0; c < channels; ++c) {
    float* row = data + c * length;
    double mean = 0.0;
    for (int64_t i = 0; i < length; ++i) mean += row[i];
    mean /= static_cast<double>(length);
    for (int64_t i = 0; i < length; ++i) row[i] -= static_cast<float>(mean);
    if (mode == 2) continue;
    double denom = 0.0;
    if (mode == 0) {
      for (int64_t i = 0; i < length; ++i)
        denom += static_cast<double>(row[i]) * row[i];
      denom = std::sqrt(denom / static_cast<double>(length));
    } else {
      denom = row[0];
      for (int64_t i = 1; i < length; ++i)
        if (row[i] > denom) denom = row[i];
    }
    if (denom == 0.0) denom = 1.0;
    float inv = static_cast<float>(1.0 / denom);
    for (int64_t i = 0; i < length; ++i) row[i] *= inv;
  }
}

// Add a (width+1)-sample label window into `out` (length L) at each index,
// with the reference's edge-truncation rules (preprocess.py:567-619):
//   idx < 0                      -> skipped
//   idx - left < 0               -> right-aligned head slice
//   idx + right <= L - 1         -> full window
//   idx <= L - 1                 -> tail slice
//   idx > L - 1                  -> skipped
void soft_label_add_f64(double* out, int64_t length, const int64_t* idxs,
                        int64_t n_idx, const double* window, int64_t width) {
  const int64_t left = width / 2;
  const int64_t right = width - left;
  for (int64_t k = 0; k < n_idx; ++k) {
    const int64_t idx = idxs[k];
    if (idx < 0 || idx > length - 1) continue;
    if (idx - left < 0) {
      int64_t count = idx + right + 1;  // head slice
      if (count > length) count = length;  // window wider than the array
      const double* w = window + (width + 1 - count);
      for (int64_t i = 0; i < count; ++i) out[i] += w[i];
    } else if (idx + right <= length - 1) {
      double* o = out + (idx - left);
      for (int64_t i = 0; i < width + 1; ++i) o[i] += window[i];
    } else {
      const int64_t count = length - (idx - left);  // tail slice
      double* o = out + (length - count);
      for (int64_t i = 0; i < count; ++i) o[i] += window[i];
    }
  }
}

}  // extern "C"
