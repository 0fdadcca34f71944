// Native host runtime for distributed_llama_tpu.
//
// The reference implements its entire host layer in C++ (loader, quant codecs,
// RNG, tokenizer — src/utils.cpp, src/quants.cpp, src/tokenizer.cpp). This
// library is our native equivalent for the host-side hot paths: the TPU compute
// path is XLA/Pallas, but bulk byte-wrangling (streaming GB-scale weight files,
// quant pack/unpack, seeded stream generation) runs here, exposed to Python via
// ctypes (see distributed_llama_tpu/utils/native.py).
//
// Build: make -C csrc   (g++ -O3 -march=native -shared -fPIC)

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <cmath>
#include <functional>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

extern "C" {

// xorshift64* stream (reference src/utils.cpp:27-38 semantics): fills out[n]
// with (float)( ((u32 >> 8) / 2^24) / divisor ), the division done in double
// like the reference test's `randomF32(&state) / 120.0` idiom. Returns the
// advanced state.
uint64_t xorshift_fill_f32(uint64_t state, float* out, int64_t n, double divisor) {
    for (int64_t i = 0; i < n; i++) {
        state ^= state >> 12;
        state ^= state << 25;
        state ^= state >> 27;
        uint32_t u = (uint32_t)((state * 0x2545F4914F6CDD1Dull) >> 32);
        float f = (float)(u >> 8) / 16777216.0f;
        out[i] = (float)((double)f / divisor);
    }
    return state;
}

// ---- f16 <-> f32 (IEEE, round-to-nearest-even on encode) -------------------

static inline float f16_to_f32(uint16_t h) {
    uint32_t s = (uint32_t)(h & 0x8000) << 16;
    uint32_t e = (h >> 10) & 0x1F;
    uint32_t m = h & 0x3FF;
    uint32_t bits;
    if (e == 0) {
        if (m == 0) {
            bits = s;
        } else {  // subnormal
            // m * 2^-24: after ``shift`` doublings bit 10 is the implicit
            // one, so the exponent is -14 - shift
            int shift = 0;
            while (!(m & 0x400)) { m <<= 1; shift++; }
            m &= 0x3FF;
            bits = s | ((127 - 14 - shift) << 23) | (m << 13);
        }
    } else if (e == 31) {
        bits = s | 0x7F800000 | (m << 13);
    } else {
        bits = s | ((e - 15 + 127) << 23) | (m << 13);
    }
    float f;
    std::memcpy(&f, &bits, 4);
    return f;
}

static inline uint16_t f32_to_f16(float f) {
    uint32_t x;
    std::memcpy(&x, &f, 4);
    uint32_t s = (x >> 16) & 0x8000;
    int32_t e = ((x >> 23) & 0xFF) - 127 + 15;
    uint32_t m = x & 0x7FFFFF;
    if (((x >> 23) & 0xFF) == 0xFF) return (uint16_t)(s | 0x7C00 | (m ? 0x200 : 0));
    if (e >= 31) return (uint16_t)(s | 0x7C00);  // overflow -> inf
    if (e <= 0) {  // subnormal or zero
        if (e < -10) return (uint16_t)s;
        m |= 0x800000;
        uint32_t shift = 14 - e;
        uint32_t half = m >> shift;
        uint32_t rem = m & ((1u << shift) - 1);
        uint32_t halfway = 1u << (shift - 1);
        if (rem > halfway || (rem == halfway && (half & 1))) half++;
        return (uint16_t)(s | half);
    }
    uint32_t half = m >> 13;
    uint32_t rem = m & 0x1FFF;
    if (rem > 0x1000 || (rem == 0x1000 && (half & 1))) {
        half++;
        if (half == 0x400) { half = 0; e++; if (e >= 31) return (uint16_t)(s | 0x7C00); }
    }
    return (uint16_t)(s | (e << 10) | half);
}

// ---- Q40 codec (wire layout: f16 delta || 16 nibble bytes per 32 values) ---

// Decode nb blocks of wire-format Q40 into f32 (reference quants.cpp:133-180
// value map: (nibble - 8) * delta; low nibbles are values 0..15, high 16..31).
void q40_decode(const uint8_t* in, float* out, int64_t nb) {
    for (int64_t b = 0; b < nb; b++) {
        const uint8_t* blk = in + b * 18;
        uint16_t d16;
        std::memcpy(&d16, blk, 2);
        float d = f16_to_f32(d16);
        float* y = out + b * 32;
        for (int j = 0; j < 16; j++) {
            uint8_t q = blk[2 + j];
            y[j] = (float)((int)(q & 0x0F) - 8) * d;
            y[j + 16] = (float)((int)(q >> 4) - 8) * d;
        }
    }
}

// Encode f32 -> wire Q40, converter.py:13-43 semantics (delta from signed
// max-magnitude / -8, reciprocal of the unrounded f32 delta, +8.5 offset,
// clamp 15, truncate).
void q40_encode(const float* in, uint8_t* out, int64_t nb) {
    for (int64_t b = 0; b < nb; b++) {
        const float* x = in + b * 32;
        float gmax = x[0], gmin = x[0];
        for (int j = 1; j < 32; j++) {
            if (x[j] > gmax) gmax = x[j];
            if (x[j] < gmin) gmin = x[j];
        }
        float delta = (-gmin > gmax ? gmin : gmax) / -8.0f;
        float id = delta != 0.0f ? 1.0f / delta : 0.0f;
        uint8_t* blk = out + b * 18;
        uint16_t d16 = f32_to_f16(delta);
        std::memcpy(blk, &d16, 2);
        int codes[32];
        for (int j = 0; j < 32; j++) {
            float q = x[j] * id + 8.5f;
            if (!(q < 15.0f)) q = 15.0f;  // NaN clamps to 15, like np.where
            codes[j] = (int)q;
        }
        for (int j = 0; j < 16; j++)
            blk[2 + j] = (uint8_t)((codes[j] & 0xF) | ((codes[j + 16] & 0xF) << 4));
    }
}

// ---- Q80 codec (f16 delta || 32 int8 per 32 values) ------------------------

void q80_decode(const uint8_t* in, float* out, int64_t nb) {
    for (int64_t b = 0; b < nb; b++) {
        const uint8_t* blk = in + b * 34;
        uint16_t d16;
        std::memcpy(&d16, blk, 2);
        float d = f16_to_f32(d16);
        const int8_t* qs = (const int8_t*)(blk + 2);
        float* y = out + b * 32;
        for (int j = 0; j < 32; j++) y[j] = (float)qs[j] * d;
    }
}

void q80_encode(const float* in, uint8_t* out, int64_t nb) {
    for (int64_t b = 0; b < nb; b++) {
        const float* x = in + b * 32;
        float amax = 0.0f;
        for (int j = 0; j < 32; j++) {
            float v = std::fabs(x[j]);
            if (v > amax) amax = v;
        }
        float d = amax / 127.0f;
        float id = d != 0.0f ? 1.0f / d : 0.0f;
        uint8_t* blk = out + b * 34;
        uint16_t d16 = f32_to_f16(d);
        std::memcpy(blk, &d16, 2);
        int8_t* qs = (int8_t*)(blk + 2);
        for (int j = 0; j < 32; j++)
            qs[j] = (int8_t)std::nearbyintf(x[j] * id);  // ties-to-even, NEON parity
    }
}

// ---- Q40 kernel-layout re-tiling (load-time, threaded) ---------------------
//
// (N, d, nb, 16) codec-layout nibble planes -> (N, 16, d, nb) kernel layout
// (ops/pallas_q40 block shape), plus the f16 -> f32 scale upconvert. This is
// the GB-scale transpose every Q40 load pays once; numpy does it
// single-threaded through a strided copy. Parallel over (n, j) output planes:
// each plane write is contiguous (d*nb bytes), reads are stride-16.

static void tile_planes(const uint8_t* qs, uint8_t* qs_t,
                        int64_t d, int64_t nb, int64_t lo, int64_t hi) {
    const int64_t plane = d * nb;
    for (int64_t w = lo; w < hi; w++) {
        const int64_t s = w / 16, j = w % 16;
        const uint8_t* src = qs + (s * plane + 0) * 16 + j;
        uint8_t* dst = qs_t + (s * 16 + j) * plane;
        for (int64_t i = 0; i < plane; i++) dst[i] = src[i * 16];
    }
}

// [0, work) in n_threads contiguous ranges, one thread each; f(lo, hi).
static void run_ranges(int64_t work, int32_t n_threads,
                       const std::function<void(int64_t, int64_t)>& f) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > work) n_threads = (int32_t)work;
    std::vector<std::thread> ts;
    ts.reserve((size_t)n_threads);
    for (int32_t t = 0; t < n_threads; t++)
        ts.emplace_back(f, work * t / n_threads, work * (t + 1) / n_threads);
    for (auto& th : ts) th.join();
}

void q40_tile_kernel_layout(const uint8_t* qs, const uint16_t* d16,
                            uint8_t* qs_t, float* scale, int64_t n_stacked,
                            int64_t d, int64_t nb, int32_t n_threads) {
    run_ranges(n_stacked * 16, n_threads, [=](int64_t lo, int64_t hi) {
        tile_planes(qs, qs_t, d, nb, lo, hi);
    });
    // scales: f16 -> f32, threaded
    run_ranges(n_stacked * d * nb, n_threads, [=](int64_t lo, int64_t hi) {
        for (int64_t i = lo; i < hi; i++) scale[i] = f16_to_f32(d16[i]);
    });
}

// nb-major sibling: (N, d, nb, 16) -> (N, 16, nb, d) codes and (N, d, nb) f16
// -> (N, nb, d) f32 scales (io/loader.Q40KernelNb: the output dim d minor).
// Work is cut into (stacked slice, band of TILE_I rows): a band's source
// lines (one per row for four adjacent blocks) stay in L1 across the 16
// nibble planes and the blocks that share them, so the source is read once
// and every write is a run of TILE_I bytes — the plane-at-a-time loop above
// reads the source 16 times.

constexpr int64_t TILE_I = 128;

static void tile_bands_nb(const uint8_t* qs, const uint16_t* d16,
                          uint8_t* qs_t, float* scale, int64_t d, int64_t nb,
                          int64_t lo, int64_t hi) {
    const int64_t bands = (d + TILE_I - 1) / TILE_I;
    for (int64_t w = lo; w < hi; w++) {
        const int64_t s = w / bands, i0 = (w % bands) * TILE_I;
        const int64_t i1 = i0 + TILE_I < d ? i0 + TILE_I : d;
        const uint8_t* src_s = qs + s * d * nb * 16;
        uint8_t* dst_s = qs_t + s * 16 * nb * d;
        const uint16_t* ssrc = d16 + s * d * nb;
        float* sdst = scale + s * nb * d;
        for (int64_t b = 0; b < nb; b++) {
            for (int64_t j = 0; j < 16; j++) {
                const uint8_t* src = src_s + b * 16 + j;
                uint8_t* dst = dst_s + (j * nb + b) * d;
                for (int64_t i = i0; i < i1; i++) dst[i] = src[i * nb * 16];
            }
            for (int64_t i = i0; i < i1; i++)
                sdst[b * d + i] = f16_to_f32(ssrc[i * nb + b]);
        }
    }
}

void q40_tile_kernel_layout_nb(const uint8_t* qs, const uint16_t* d16,
                               uint8_t* qs_t, float* scale,
                               int64_t n_stacked, int64_t d, int64_t nb,
                               int32_t n_threads) {
    run_ranges(n_stacked * ((d + TILE_I - 1) / TILE_I), n_threads,
               [=](int64_t lo, int64_t hi) {
                   tile_bands_nb(qs, d16, qs_t, scale, d, nb, lo, hi);
               });
}

// ---- BPE tokenizer encode (reference src/tokenizer.cpp:84-204 semantics) ---
//
// The reference's tokenizer is C++; this is our native equivalent of its hot
// path, `encode`: UTF-8 codepoint split with byte-fallback (+3), then greedy
// highest-score pair merging. The vocab is handed over once as a concatenated
// blob + offsets + scores (built by the Python Tokenizer after parsing
// tokenizer.bin); lookups use a piece -> first-id hash map.

struct TokVocab {
    std::vector<std::string> pieces;
    std::vector<float> scores;
    std::unordered_map<std::string, int32_t> lookup;  // first occurrence wins
};

void* tok_create(const uint8_t* blob, const int64_t* offsets,
                 const float* scores, int32_t n) {
    TokVocab* v = new TokVocab();
    v->pieces.reserve(n);
    v->scores.assign(scores, scores + n);
    for (int32_t i = 0; i < n; i++) {
        v->pieces.emplace_back((const char*)(blob + offsets[i]),
                               (size_t)(offsets[i + 1] - offsets[i]));
        v->lookup.emplace(v->pieces.back(), i);  // keeps first id on dup
    }
    return v;
}

void tok_destroy(void* handle) { delete (TokVocab*)handle; }

// Returns the token count (<= out_cap guaranteed: one token per input byte
// upper bound). out receives ids; bos/dummy-space/eos handling stays in
// Python (trivial, and the dummy-space id depends on lookup state there).
int64_t tok_encode(void* handle, const uint8_t* text, int64_t len,
                   int32_t* out) {
    TokVocab* v = (TokVocab*)handle;
    std::vector<int32_t> toks;
    toks.reserve((size_t)len);

    // UTF-8 codepoint split (max 4 bytes), byte-fallback (+3) on miss
    int64_t i = 0;
    while (i < len) {
        int64_t j = i + 1;
        while (j < len && (text[j] & 0xC0) == 0x80 && j - i < 4) j++;
        std::string chunk((const char*)(text + i), (size_t)(j - i));
        auto it = v->lookup.find(chunk);
        if (it != v->lookup.end()) {
            toks.push_back(it->second);
        } else {
            for (int64_t b = i; b < j; b++)
                toks.push_back((int32_t)text[b] + 3);
        }
        i = j;
    }

    // greedy highest-score merges (reference tokenizer.cpp:169-194)
    const int32_t n_pieces = (int32_t)v->pieces.size();
    while (true) {
        float best_score = -1e10f;
        int32_t best_id = -1;
        int64_t best_idx = -1;
        for (int64_t k = 0; k + 1 < (int64_t)toks.size(); k++) {
            // byte-fallback ids (byte + 3) have no piece when the vocab
            // is smaller than 259: they can never merge, and indexing
            // pieces[] with them reads out of bounds (ASan-found)
            if (toks[(size_t)k] >= n_pieces
                || toks[(size_t)k + 1] >= n_pieces) continue;
            std::string merged = v->pieces[(size_t)toks[(size_t)k]]
                               + v->pieces[(size_t)toks[(size_t)k + 1]];
            auto it = v->lookup.find(merged);
            if (it != v->lookup.end() && v->scores[(size_t)it->second] > best_score) {
                best_score = v->scores[(size_t)it->second];
                best_id = it->second;
                best_idx = k;
            }
        }
        if (best_idx == -1) break;
        toks[(size_t)best_idx] = best_id;
        toks.erase(toks.begin() + best_idx + 1);
    }

    std::memcpy(out, toks.data(), toks.size() * sizeof(int32_t));
    return (int64_t)toks.size();
}

// ---- Sampler (reference src/tokenizer.cpp:206-319 semantics) ---------------
//
// The reference's sampler is C++; this is the native host equivalent of
// runtime/sampling.py (which stays as the no-toolchain fallback and the
// documentation of record for the semantics): temperature == 0 -> argmax;
// else logits/temp -> max-subtracted f32 softmax -> nucleus top-p with the
// (1-p)/(n-1) cutoff pre-filter and stable descending sort, or the plain
// multinomial CDF walk when topp is outside (0, 1). The xorshift coin is
// drawn by the caller (Python owns the RNG stream / checkpoint contract).

int32_t sample_logits(const float* logits, int32_t n, float temperature,
                      float topp, float coin) {
    if (temperature == 0.0f) {
        int32_t best = 0;
        for (int32_t i = 1; i < n; i++)
            if (logits[i] > logits[best]) best = i;  // first max, like argmax
        return best;
    }
    std::vector<float> probs((size_t)n);
    float mx = logits[0] / temperature;
    for (int32_t i = 1; i < n; i++) {
        float v = logits[i] / temperature;
        if (v > mx) mx = v;
    }
    float sum = 0.0f;
    for (int32_t i = 0; i < n; i++) {
        probs[(size_t)i] = std::exp(logits[i] / temperature - mx);
        sum += probs[(size_t)i];
    }
    for (int32_t i = 0; i < n; i++) probs[(size_t)i] /= sum;

    if (topp <= 0.0f || topp >= 1.0f) {  // multinomial CDF walk
        float cdf = 0.0f;
        for (int32_t i = 0; i < n; i++) {
            cdf += probs[(size_t)i];
            if (coin < cdf) return i;
        }
        return n - 1;
    }

    // nucleus: cutoff pre-filter, stable descending sort, cut at cum > topp,
    // CDF walk over the kept prefix scaled by coin*cum
    if (n == 1) return 0;
    float cutoff = (1.0f - topp) / (float)(n - 1);
    std::vector<int32_t> order;
    order.reserve((size_t)n);
    for (int32_t i = 0; i < n; i++)
        if (probs[(size_t)i] >= cutoff) order.push_back(i);
    if (order.empty()) {
        // degenerate nucleus (topp < 1/n with near-uniform probs): the
        // smallest keepable set is the single most-probable token
        int32_t best = 0;
        for (int32_t i = 1; i < n; i++)
            if (probs[(size_t)i] > probs[(size_t)best]) best = i;
        return best;
    }
    std::stable_sort(order.begin(), order.end(),
                     [&](int32_t a, int32_t b) {
                         return probs[(size_t)a] > probs[(size_t)b];
                     });
    float cum = 0.0f;
    int64_t last = (int64_t)order.size() - 1;
    for (int64_t i = 0; i < (int64_t)order.size(); i++) {
        cum += probs[(size_t)order[(size_t)i]];
        if (cum > topp) { last = i; break; }
    }
    float r = coin * cum;
    float cdf = 0.0f;
    for (int64_t i = 0; i <= last; i++) {
        cdf += probs[(size_t)order[(size_t)i]];
        if (r < cdf) return order[(size_t)i];
    }
    return order[(size_t)last];
}

}  // extern "C"
