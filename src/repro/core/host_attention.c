/* Host paged decode attention: the flash partial of one (row, page block)
 * task in one pass over its KV pages.
 *
 * Built by repro/core/host_attention.py on first use (no -ffast-math: its
 * start-up code would set flush-to-zero for the whole process) and called
 * through ctypes, which releases the GIL for the call. Every number is
 * float32: a bf16 element is widened exactly (its bits are the upper half
 * of the float32 of the same value), a float32 pool is read as it is.
 *
 * The pool is [P, page, KV, hd] for one layer, addressed by element strides
 * (page, token, kv head; hd contiguous), so a kv-head slice of a pool runs
 * without a copy. Each KV head's arithmetic reads only that head's q, K and
 * V, in an order this source fixes (GCC vector extensions, no reassociation
 * by the compiler): a head gives the same bits whichever other heads, rows
 * or threads share the call, and whichever tile of tokens it falls in.
 */
#include <math.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

/* Floats per vector: a machine register's width, as wider generic vectors
 * compile to slow code without AVX-512. head_dim must be a multiple. */
#ifdef __AVX512F__
#define VL 16
#else
#define VL 8
#endif
#define PF 8 /* tokens to prefetch ahead: each page is a separate stream */

typedef float vf __attribute__((vector_size(VL * 4)));
typedef int32_t vi __attribute__((vector_size(VL * 4)));
typedef uint16_t vh __attribute__((vector_size(VL * 2)));
typedef uint32_t vu __attribute__((vector_size(VL * 4)));
typedef float v8 __attribute__((vector_size(32)));
typedef float v4 __attribute__((vector_size(16)));
#define INLINE static inline __attribute__((always_inline))

INLINE vf loadf(const float *p) {
    vf f;
    memcpy(&f, p, sizeof f);
    return f;
}

INLINE void storef(float *p, vf f) { memcpy(p, &f, sizeof f); }

/* VL elements of a K or V row as float32. */
INLINE vf load_wide(const char *p, int bf16) {
    if (bf16) {
        vh h;
        memcpy(&h, p, sizeof h);
        return (vf)(__builtin_convertvector(h, vu) << 16);
    }
    return loadf((const float *)p);
}

/* The lanes' sum, as a fixed tree. */
INLINE float hsum8(v8 a) {
    const v4 c = __builtin_shufflevector(a, a, 0, 1, 2, 3)
                 + __builtin_shufflevector(a, a, 4, 5, 6, 7);
    return (c[0] + c[2]) + (c[1] + c[3]);
}

INLINE float hsum(vf v) {
#if VL == 16
    return hsum8(__builtin_shufflevector(v, v, 0, 1, 2, 3, 4, 5, 6, 7)
                 + __builtin_shufflevector(v, v, 8, 9, 10, 11, 12, 13, 14, 15));
#else
    return hsum8(v);
#endif
}

/* The larger of each lane, as `a > b ? a : b`. */
INLINE vf vmax(vf a, vf b) {
    const vi gt = a > b;
    return (vf)(((vi)a & gt) | ((vi)b & ~gt));
}

/* exp(x) of each lane (x <= 0 here: a score less the row's max), in
 * float32: Cephes' expf (Cody-Waite reduction by ln 2 and a degree-6
 * polynomial, about 1 ulp), and 0 below -87.3, where exp leaves the
 * normal floats. */
INLINE vf vexp(vf x) {
    const vf lo = (vf){} - 87.3365f;
    const vi keep = x >= lo;
    x = vmax(x, lo);
    const vf fx = x * 1.44269504088896341f + 0.5f;
    vi n = __builtin_convertvector(fx, vi); /* toward zero; floor below */
    n += (vi)(__builtin_convertvector(n, vf) > fx);
    const vf fn = __builtin_convertvector(n, vf);
    x = x - fn * 0.693359375f - fn * -2.12194440e-4f;
    const vf z = x * x;
    vf y = (vf){} + 1.9875691500e-4f;
    y = y * x + 1.3981999507e-3f;
    y = y * x + 8.3334519073e-3f;
    y = y * x + 4.1665795894e-2f;
    y = y * x + 1.6666665459e-1f;
    y = y * x + 5.0000001201e-1f;
    y = y * z + x + 1.0f;
    return (vf)((vi)(y * (vf)((n + 127) << 23)) & keep);
}

/* Scores of a token for H heads sit in a row of H rounded up to VL. */
INLINE int64_t padded(int64_t H) { return (H + VL - 1) / VL * VL; }

/* One of K or V for the task: token t (counted from the first unmasked
 * token) of head h starts at at(r, t) + h * head. */
typedef struct {
    const char *base;
    const int64_t *ids;
    int64_t first, page, s_page, s_tok, head, esz, kv, hd, T;
} rows_t;

INLINE const char *at(const rows_t *r, int64_t t) {
    const int64_t i = r->first + t;
    return r->base + (r->ids[i / r->page] * r->s_page + i % r->page * r->s_tok)
                         * r->esz;
}

INLINE void prefetch(const rows_t *r, int64_t t) {
    if (t >= r->T)
        return;
    const char *p = at(r, t);
    for (int64_t h = 0; h < r->kv; h++)
        for (int64_t b = 0; b < r->hd * r->esz; b += 64)
            __builtin_prefetch(p + h * r->head + b);
}

/* Widen head h of nt tokens from t into rows [nt][hd]. */
INLINE void widen(const rows_t *r, int64_t t, int nt, int64_t h, int bf16,
                  vf *rows) {
    const int64_t C = r->hd / VL;
    for (int u = 0; u < nt; u++) {
        const char *src = at(r, t + u) + h * r->head;
        prefetch(r, t + u + PF);
        for (int64_t c = 0; c < C; c++)
            rows[u * C + c] = load_wide(src + c * VL * r->esz, bf16);
    }
}

/* s [T][padded(H)] = scale * q.k for 4 tokens (a tile) or 1 (the tail):
 * each dot is one chain over the row's vectors, then hsum. */
INLINE void scores(const rows_t *K, int64_t t, int nt, int bf16,
                   const float *q, int64_t g, float scale, float *s,
                   vf *rows) {
    const int64_t C = K->hd / VL, H = padded(K->kv * g);
    for (int64_t h = 0; h < K->kv; h++) {
        widen(K, t, nt, h, bf16, rows);
        for (int64_t j = 0; j < g; j++) {
            const float *qj = q + (h * g + j) * K->hd;
            float *sj = s + t * H + h * g + j;
            if (nt == 4) {
                vf a0 = {0}, a1 = {0}, a2 = {0}, a3 = {0};
                for (int64_t c = 0; c < C; c++) {
                    const vf x = loadf(qj + c * VL);
                    a0 += x * rows[c];
                    a1 += x * rows[C + c];
                    a2 += x * rows[2 * C + c];
                    a3 += x * rows[3 * C + c];
                }
                sj[0] = hsum(a0) * scale;
                sj[H] = hsum(a1) * scale;
                sj[2 * H] = hsum(a2) * scale;
                sj[3 * H] = hsum(a3) * scale;
            } else {
                vf a0 = {0};
                for (int64_t c = 0; c < C; c++)
                    a0 += loadf(qj + c * VL) * rows[c];
                sj[0] = hsum(a0) * scale;
            }
        }
    }
}

/* acc [H][hd] += e * v over nt tokens from t, token by token. */
INLINE void values(const rows_t *V, int64_t t, int nt, int bf16,
                   const float *s, int64_t g, float *acc, vf *rows) {
    const int64_t C = V->hd / VL, H = padded(V->kv * g);
    for (int64_t h = 0; h < V->kv; h++) {
        widen(V, t, nt, h, bf16, rows);
        for (int64_t j = 0; j < g; j++) {
            const float *e = s + t * H + h * g + j;
            float *a = acc + (h * g + j) * V->hd;
            for (int64_t c = 0; c < C; c++) {
                vf x = loadf(a + c * VL);
                for (int u = 0; u < nt; u++)
                    x += e[u * H] * rows[u * C + c];
                storef(a + c * VL, x);
            }
        }
    }
}

/* The geometry of one call: the layer's pools, the rows' queries and page
 * tables. Strides are in elements. */
typedef struct {
    const void *k, *v;
    int64_t k_page, k_tok, k_head, v_page, v_tok, v_head;
    int bf16;
    int64_t page, kv, g, hd;
    float scale;
    const float *q;        /* [R][kv*g][hd] */
    const int64_t *tables; /* [R][mp] page ids inside the pool */
    int64_t mp;
} call_t;

/* The flash partial of row i's q [kv, g, hd] over tokens
 * [max(lo, start_tok), hi), held by the row's pages from p0 (which holds
 * token lo): acc [kv*g, hd] = sum_t e_t v_t, l [kv*g] = sum_t e_t,
 * m [kv*g] = max_t s_t, with s_t = scale * q.k_t and e_t = exp(s_t - m).
 * Returns 0; -1 for no token or no memory for the scores. */
static int block(const call_t *c, int64_t i, int64_t p0, int64_t lo,
                 int64_t hi, int64_t start_tok, float *acc, float *l,
                 float *m) {
    const int64_t first = start_tok > lo ? start_tok - lo : 0;
    const int64_t T = hi - lo - first, kv = c->kv, g = c->g, hd = c->hd;
    const int64_t H = kv * g, Hp = padded(H), W = Hp / VL;
    const int64_t esz = c->bf16 ? 2 : 4;
    const int64_t *ids = c->tables + i * c->mp + p0;
    const float *q = c->q + i * H * hd;
    const int bf16 = c->bf16;
    const float scale = c->scale;
    if (T <= 0)
        return -1;
    float *s = calloc((size_t)(T * Hp), sizeof(float));
    vf *rows = aligned_alloc(sizeof(vf),
                             sizeof(float) * (size_t)(4 * hd + 2 * Hp));
    if (!s || !rows) {
        free(s);
        free(rows);
        return -1;
    }
    const rows_t K = {c->k, ids, first, c->page, c->k_page, c->k_tok,
                      c->k_head * esz, esz, kv, hd, T};
    const rows_t V = {c->v, ids, first, c->page, c->v_page, c->v_tok,
                      c->v_head * esz, esz, kv, hd, T};
    int64_t t;

    for (t = 0; t < PF; t++)
        prefetch(&K, t);
    for (t = 0; t + 4 <= T; t += 4)
        scores(&K, t, 4, bf16, q, g, scale, s, rows);
    for (; t < T; t++)
        scores(&K, t, 1, bf16, q, g, scale, s, rows);

    for (t = 0; t < PF; t++)
        prefetch(&V, t);
    /* softmax numerators, each head's lanes in token order */
    vf *mx = rows + 4 * hd / VL, *sum = mx + W;
    for (int64_t w = 0; w < W; w++) {
        mx[w] = (vf){} - INFINITY;
        sum[w] = (vf){};
    }
    for (t = 0; t < T; t++)
        for (int64_t w = 0; w < W; w++)
            mx[w] = vmax(loadf(s + t * Hp + w * VL), mx[w]);
    for (t = 0; t < T; t++)
        for (int64_t w = 0; w < W; w++) {
            const vf e = vexp(loadf(s + t * Hp + w * VL) - mx[w]);
            storef(s + t * Hp + w * VL, e);
            sum[w] += e;
        }
    memcpy(m, mx, sizeof(float) * (size_t)H);
    memcpy(l, sum, sizeof(float) * (size_t)H);

    memset(acc, 0, sizeof(float) * (size_t)(H * hd));
    for (t = 0; t + 4 <= T; t += 4)
        values(&V, t, 4, bf16, s, g, acc, rows);
    for (; t < T; t++)
        values(&V, t, 1, bf16, s, g, acc, rows);
    free(rows);
    free(s);
    return 0;
}

/* out [H, hd] from the partials of tasks [j0, j1), in block order. */
static void merge(int64_t j0, int64_t j1, int64_t H, int64_t hd,
                  const float *acc, const float *l, const float *m,
                  float *out) {
    for (int64_t h = 0; h < H; h++) {
        float mx = m[j0 * H + h], den = 0.0f;
        for (int64_t j = j0 + 1; j < j1; j++)
            mx = m[j * H + h] > mx ? m[j * H + h] : mx;
        float *o = out + h * hd;
        memset(o, 0, sizeof(float) * (size_t)hd);
        for (int64_t j = j0; j < j1; j++) {
            const float corr = expf(m[j * H + h] - mx);
            const float *a = acc + (j * H + h) * hd;
            for (int64_t d = 0; d < hd; d++)
                o[d] += a[d] * corr;
            den += l[j * H + h] * corr;
        }
        den = den > 1e-30f ? den : 1e-30f;
        for (int64_t d = 0; d < hd; d++)
            o[d] /= den;
    }
}

/* One worker's share of a call: take tasks off the shared counter *next,
 * in the order order[], until none is left. Task j is (row, first page,
 * lo, hi, start_tok) at tasks[5j] and writes its partial to acc, l, m at
 * j; row r owns tasks [first[r], first[r + 1]), and whoever finishes a
 * row's last task merges the row into out [R][H][hd] (remaining [R] counts
 * each row's unfinished tasks). Every worker of a call passes the same
 * arguments. Returns 0; -1 where a task failed, -2 for a head_dim that is
 * not a multiple of VL. */
int hattn_run(const void *k, const void *v, int64_t k_page, int64_t k_tok,
              int64_t k_head, int64_t v_page, int64_t v_tok, int64_t v_head,
              int bf16, int64_t page, int64_t kv, int64_t g, int64_t hd,
              float scale, const float *q, const int64_t *tables, int64_t mp,
              const int64_t *tasks, int64_t n_tasks, const int64_t *first,
              const int64_t *order, int64_t *next, int64_t *remaining,
              float *acc, float *l, float *m, float *out) {
    if (hd <= 0 || hd % VL || kv <= 0 || g <= 0)
        return -2;
    const call_t c = {k, v, k_page, k_tok, k_head, v_page, v_tok, v_head,
                      bf16, page, kv, g, hd, scale, q, tables, mp};
    const int64_t H = kv * g;
    for (;;) {
        const int64_t o = __atomic_fetch_add(next, 1, __ATOMIC_RELAXED);
        if (o >= n_tasks)
            return 0;
        const int64_t j = order[o];
        const int64_t *tk = tasks + 5 * j, r = tk[0];
        if (block(&c, r, tk[1], tk[2], tk[3], tk[4], acc + j * H * hd,
                  l + j * H, m + j * H))
            return -1;
        if (__atomic_sub_fetch(remaining + r, 1, __ATOMIC_ACQ_REL) == 0)
            merge(first[r], first[r + 1], H, hd, acc, l, m,
                  out + r * H * hd);
    }
}
