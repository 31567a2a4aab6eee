/* Plain serial reference of Peregrine's exact-mode feature computation.
 *
 * One packet at a time, in stream order, per key type: decay the slot's
 * atoms by 2^(-lambda*dt), add the packet, and at every record position
 * (the packet that closes an epoch) write the 80 statistics.  Dense slot
 * tables with no collision resolution: flows that hash to one slot share
 * it, as the switch's register arrays do.
 *
 * The stream is a pool of P packets replayed in laps: packet i is pool
 * entry i % P with timestamp (float)(ts_base[i % P] + lap * span), lap =
 * i / P, which is the float32 value the system under test is given.
 *
 * prec 0 computes in float64; prec 1 rounds every stored value and every
 * arithmetic result to bfloat16 (the precision control).
 *
 * Work is split over threads by key type and slot residue: thread (k, r)
 * owns the slots s of key type k with s % R == r, so no two threads touch
 * one slot and each record column is written by exactly one thread.
 */
#include <math.h>
#include <pthread.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#define ND 4
#define NF 80
static const double LAM[ND] = {10.0, 1.0, 0.1, 1.0 / 60.0};

static inline double q(double x, int bf) {
    if (!bf) return x;
    float f = (float)x;
    uint32_t u;
    memcpy(&u, &f, 4);
    if ((u & 0x7f800000u) != 0x7f800000u) {
        u += 0x7fffu + ((u >> 16) & 1u);
        u &= 0xffff0000u;
    }
    memcpy(&f, &u, 4);
    return (double)f;
}

static inline double dv(double a, double b, int bf) {
    return b > 0.0 ? q(a / (b > 1e-12 ? b : 1e-12), bf) : 0.0;
}

static inline double decay(int d, double t, double last, int bf) {
    if (last < 0.0) return 0.0;
    double dt = q(t - last, bf);
    if (dt < 0.0) dt = 0.0;
    return q(exp2(q(-LAM[d] * dt, bf)), bf);
}

typedef struct {
    /* stream */
    int64_t P, n_gen, n_rec;
    const int32_t *slot;     /* this key type's slot per pool entry */
    const uint8_t *dirb;     /* channel direction bit per pool entry */
    const float *length;
    const double *ts_base;
    double span;
    int32_t epoch;
    int bf;
    /* ownership */
    int key, R, r;
    int64_t n_own;           /* slots owned: ceil((n_slots - r) / R) */
    double *out;             /* (n_rec, NF) */
    int err;
} job_t;

/* uni slot: last_t[4] w[4] ls[4] ss[4] */
#define UNI_W 16
/* bi slot: per dir (last_t[4] w[4] ls[4] ss[4]) x2, sr[4], sr_last_t[4],
 * res_last[2][4] */
#define BI_W 48

static void *run_job(void *arg) {
    job_t *j = (job_t *)arg;
    const int bi = j->key >= 2;
    const int64_t width = bi ? BI_W : UNI_W;
    double *tab = (double *)malloc((size_t)(j->n_own * width) * sizeof(double));
    if (!tab) { j->err = 1; return NULL; }
    for (int64_t s = 0; s < j->n_own; s++) {
        double *e = tab + s * width;
        memset(e, 0, (size_t)width * sizeof(double));
        if (bi) {
            for (int d = 0; d < ND; d++) {
                e[d] = -1.0; e[16 + d] = -1.0; e[36 + d] = -1.0;
            }
        } else {
            for (int d = 0; d < ND; d++) e[d] = -1.0;
        }
    }
    const int bf = j->bf;
    int64_t lap = 0, e_i = -1;
    for (int64_t i = 0; i < j->n_gen; i++) {
        if (++e_i == j->P) { e_i = 0; lap++; }
        const int32_t s = j->slot[e_i];
        if (s % j->R != j->r) continue;
        double *e = tab + (int64_t)(s / j->R) * width;
        const double t = (double)(float)(j->ts_base[e_i] + (double)lap * j->span);
        const double len = q((double)j->length[e_i], bf);
        const int is_rec = ((i + 1) % j->epoch) == 0;
        const int64_t rec = (i + 1) / j->epoch - 1;
        double *o = (is_rec && rec < j->n_rec) ? j->out + rec * NF : NULL;
        if (!bi) {
            double *lt = e, *w = e + 4, *ls = e + 8, *ss = e + 12;
            for (int d = 0; d < ND; d++) {
                const double dl = decay(d, t, lt[d], bf);
                w[d] = q(q(w[d] * dl, bf) + 1.0, bf);
                ls[d] = q(q(ls[d] * dl, bf) + len, bf);
                ss[d] = q(q(ss[d] * dl, bf) + q(len * len, bf), bf);
                lt[d] = q(t, bf);
                if (o) {
                    const double mu = dv(ls[d], w[d], bf);
                    const double ex2 = dv(ss[d], w[d], bf);
                    const double var = fabs(q(ex2 - q(mu * mu, bf), bf));
                    double *c = o + j->key * 12 + d * 3;
                    c[0] = w[d]; c[1] = mu; c[2] = q(sqrt(var), bf);
                }
            }
        } else {
            const int dd = j->dirb[e_i], od = 1 - dd;
            double *lt = e + dd * 16, *w = lt + 4, *ls = lt + 8, *ss = lt + 12;
            const double *w_p = e + od * 16 + 4, *ls_p = e + od * 16 + 8,
                         *ss_p = e + od * 16 + 12;
            double *sr = e + 32, *sr_lt = e + 36, *res = e + 40;
            for (int d = 0; d < ND; d++) {
                const double dl = decay(d, t, lt[d], bf);
                w[d] = q(q(w[d] * dl, bf) + 1.0, bf);
                ls[d] = q(q(ls[d] * dl, bf) + len, bf);
                ss[d] = q(q(ss[d] * dl, bf) + q(len * len, bf), bf);
                lt[d] = q(t, bf);
                const double mu_o = dv(ls[d], w[d], bf);
                const double var_o = fabs(q(dv(ss[d], w[d], bf) - q(mu_o * mu_o, bf), bf));
                const double dsr = decay(d, t, sr_lt[d], bf);
                const double r_own = q(len - mu_o, bf);
                const double r_opp = res[od * 4 + d];
                sr[d] = q(q(sr[d] * dsr, bf) + q(r_own * r_opp, bf), bf);
                sr_lt[d] = q(t, bf);
                res[dd * 4 + d] = r_own;
                if (o) {
                    const double mu_p = dv(ls_p[d], w_p[d], bf);
                    const double var_p = fabs(q(dv(ss_p[d], w_p[d], bf) - q(mu_p * mu_p, bf), bf));
                    const double sig_o = q(sqrt(var_o), bf), sig_p = q(sqrt(var_p), bf);
                    double *c = o + 24 + (j->key - 2) * 28 + d * 7;
                    c[0] = w[d];
                    c[1] = mu_o;
                    c[2] = sig_o;
                    c[3] = q(sqrt(q(q(mu_o * mu_o, bf) + q(mu_p * mu_p, bf), bf)), bf);
                    c[4] = q(sqrt(q(q(var_o * var_o, bf) + q(var_p * var_p, bf), bf)), bf);
                    c[5] = dv(sr[d], q(w[d] + w_p[d], bf), bf);
                    c[6] = dv(c[5], q(sig_o * sig_p, bf), bf);
                }
            }
        }
    }
    free(tab);
    return NULL;
}

/* slots: (4, P) int32, key-type major.  Returns the number of records
 * written (records past n_rec are dropped), or -1 on failure. */
int64_t fc_reference(int64_t P, const int32_t *slots, const uint8_t *dirb,
                     const float *length, const double *ts_base, double span,
                     int64_t n_gen, int32_t n_slots, int32_t epoch,
                     int prec, int64_t n_rec, double *out, int threads) {
    int R = threads / 4;
    if (R < 1) R = 1;
    const int nj = 4 * R;
    job_t *jobs = (job_t *)calloc((size_t)nj, sizeof(job_t));
    pthread_t *th = (pthread_t *)calloc((size_t)nj, sizeof(pthread_t));
    if (!jobs || !th) { free(jobs); free(th); return -1; }
    for (int k = 0; k < 4; k++) {
        for (int r = 0; r < R; r++) {
            job_t *j = &jobs[k * R + r];
            j->P = P; j->n_gen = n_gen; j->n_rec = n_rec;
            j->slot = slots + (int64_t)k * P; j->dirb = dirb;
            j->length = length; j->ts_base = ts_base; j->span = span;
            j->epoch = epoch; j->bf = prec; j->key = k; j->R = R; j->r = r;
            j->n_own = (n_slots - r + R - 1) / R;
            j->out = out;
        }
    }
    for (int i = 0; i < nj; i++) pthread_create(&th[i], NULL, run_job, &jobs[i]);
    int err = 0;
    for (int i = 0; i < nj; i++) {
        pthread_join(th[i], NULL);
        err |= jobs[i].err;
    }
    free(jobs);
    free(th);
    if (err) return -1;
    int64_t made = n_gen / epoch;
    return made < n_rec ? made : n_rec;
}
